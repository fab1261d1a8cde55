"""Differential tests for the capacity-based grouped GEMM (reference
analog: group_gemm.py tested against per-expert torch.matmul loops)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from triton_dist_tpu.kernels.group_gemm import grouped_gemm, grouped_gemm_ref


@pytest.mark.parametrize("E,C,D,F", [(4, 8, 32, 64), (2, 256, 128, 512),
                                     (8, 16, 64, 128), (3, 100, 64, 96)])
def test_grouped_gemm_vs_ref(E, C, D, F):
    rng = np.random.RandomState(E + C)
    x = jnp.asarray(rng.randn(E, C, D), jnp.float32)
    w = jnp.asarray(rng.randn(E, D, F), jnp.float32)
    with jax.default_matmul_precision("highest"):
        out = grouped_gemm(x, w)
        ref = grouped_gemm_ref(x, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=1e-5)


# ----------------------------------------------------------------------
# ragged: groups padded to the row tile, nothing dropped
# ----------------------------------------------------------------------

@pytest.mark.parametrize("swiglu", [False, True])
@pytest.mark.parametrize("R,G,bm,skew", [
    (50, 4, 8, None),      # an even draw, some rows on no group
    (64, 4, 8, 1),         # every row on ONE group: the worst case of one
    (40, 3, 16, "none"),   # no row on any group: every tile skipped
])
def test_ragged_grouped_gemm_vs_ref(R, G, bm, skew, swiglu):
    from triton_dist_tpu.kernels.group_gemm import (
        group_rows_ragged, ragged_grouped_gemm, ragged_grouped_gemm_ref)
    rng = np.random.RandomState(R + G)
    K, F = 64, 256
    gid = rng.randint(0, G + 1, size=R)
    if skew == "none":
        gid[:] = G
    elif skew is not None:
        gid[:] = skew
    x = rng.randn(R, K).astype(np.float32)
    w = (rng.randn(G, K, F) * 0.1).astype(np.float32)
    g = group_rows_ragged(jnp.asarray(gid, jnp.int32), G, bm)
    assert g.rows == -(-(R + G * (bm - 1)) // bm) * bm     # never drops
    src = np.asarray(g.src)
    xs = jnp.where((g.src >= 0)[:, None],
                   jnp.asarray(x)[np.maximum(src, 0)], 0.0)
    with jax.default_matmul_precision("highest"):
        out = np.asarray(ragged_grouped_gemm(xs, jnp.asarray(w), g,
                                             swiglu=swiglu, block_f=128))
        ref = np.asarray(ragged_grouped_gemm_ref(xs, jnp.asarray(w), g,
                                                 swiglu=swiglu))
    valid = gid < G
    dest = np.asarray(g.dest)
    # every row that has a group sits in a tile of that group, once
    assert len(set(dest[valid])) == valid.sum()
    assert (np.asarray(g.tile_group)[dest[valid] // bm] == gid[valid]).all()
    assert int(g.n_used[0]) == sum(-(-(gid == e).sum() // bm)
                                   for e in range(G))
    want = np.einsum("rk,rkf->rf", x[valid], w[gid[valid]])
    if swiglu:
        a, b = np.split(want, 2, axis=-1)
        want = a / (1.0 + np.exp(-a)) * b
    np.testing.assert_allclose(out[dest[valid]], want, atol=1e-4,
                               rtol=1e-4)
    np.testing.assert_allclose(out[dest[valid]], ref[dest[valid]],
                               atol=1e-5)
