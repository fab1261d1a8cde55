"""Distributed flash-decode tests (reference analog:
test/nvidia/test_decode_attn.py's multi-rank cases — split-KV partials
per rank + inter-rank LSE combine vs a full-KV oracle)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from triton_dist_tpu.kernels.sp_flash_decode import (sp_flash_decode,
                                                     sp_flash_decode_ref)

mesh = None


def setup_module(module):
    global mesh
    n = len(jax.devices())
    mesh = jax.make_mesh((n,), ("sp",))


def _mk(B, S, Hq, Hkv, T, d, seed=0):
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(B, S, Hq, d), jnp.float32) * 0.5
    k = jnp.asarray(rng.randn(B, Hkv, T, d), jnp.float32) * 0.5
    v = jnp.asarray(rng.randn(B, Hkv, T, d), jnp.float32) * 0.5
    kv_spec = NamedSharding(mesh, P(None, None, "sp", None))
    # (replicated copies kept for the oracle; the op gets sharded views)
    return (q, k, v,
            jax.device_put(k, kv_spec), jax.device_put(v, kv_spec))


@pytest.mark.parametrize("combine", ["xla", "dist"])
@pytest.mark.parametrize(
    "B,S,Hq,Hkv,T,d,kv_len",
    [
        (2, 1, 8, 4, 1024, 128, 700),   # decode, cache spans 6/8 chips
        (2, 1, 8, 8, 512, 64, 512),     # MHA, cache exactly full
        (1, 4, 8, 2, 512, 64, 100),     # multi-token verify step,
                                        # valid KV confined to chip 0-1
    ])
def test_sp_flash_decode_vs_oracle(combine, B, S, Hq, Hkv, T, d, kv_len):
    q, k, v, ks, vs = _mk(B, S, Hq, Hkv, T, d, seed=B + T)
    with jax.default_matmul_precision("highest"):
        out = jax.jit(lambda q, k, v: sp_flash_decode(
            q, k, v, kv_len, mesh=mesh, combine=combine))(q, ks, vs)
        ref = sp_flash_decode_ref(q, k, v, kv_len)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=5e-5, rtol=1e-5)


def test_kv_cache_scatter():
    """One-sided block scatter == writing positions [0, S) of the cache;
    rows >= S keep their old contents (aliased output)."""
    from triton_dist_tpu.kernels.sp_flash_decode import kv_cache_scatter
    n = mesh.shape["sp"]
    B, Hkv, d = 2, 4, 128
    S, T = 8 * n, 32 * n
    rng = np.random.RandomState(5)
    old = jnp.asarray(rng.randn(B, Hkv, T, d), jnp.float32)
    new = jnp.asarray(rng.randn(B, Hkv, S, d), jnp.float32)
    spec = NamedSharding(mesh, P(None, None, "sp", None))
    cache = jax.device_put(old, spec)
    new_s = jax.device_put(new, spec)
    out = jax.jit(lambda c, k: kv_cache_scatter(c, k, mesh=mesh))(
        cache, new_s)
    got = np.asarray(out)
    np.testing.assert_array_equal(got[:, :, :S], np.asarray(new))
    np.testing.assert_array_equal(got[:, :, S:], np.asarray(old)[:, :, S:])


def test_sp_ref_per_slot_kv_lens():
    """Serving-oracle satellite (ISSUE 14): sp_flash_decode_ref covers
    per-slot kv_lens batches — slot b attends exactly kv_lens[b]
    positions of its own streams, independent of its neighbours. The
    paged sp serving attend lands against THIS pinned oracle."""
    B, S, Hq, Hkv, T, d = 3, 1, 4, 2, 256, 64
    rng = np.random.RandomState(11)
    q = jnp.asarray(rng.randn(B, S, Hq, d), jnp.float32) * 0.5
    k = jnp.asarray(rng.randn(B, Hkv, T, d), jnp.float32) * 0.5
    v = jnp.asarray(rng.randn(B, Hkv, T, d), jnp.float32) * 0.5
    kv_lens = jnp.asarray([7, 200, 33], jnp.int32)
    out = sp_flash_decode_ref(q, k, v, kv_lens)
    # row b must equal a batch-1 oracle at ITS OWN scalar length
    for b in range(B):
        one = sp_flash_decode_ref(q[b:b + 1], k[b:b + 1], v[b:b + 1],
                                  int(kv_lens[b]))
        np.testing.assert_allclose(np.asarray(out[b]),
                                   np.asarray(one[0]),
                                   atol=1e-6, rtol=1e-6,
                                   err_msg=f"slot {b}")


def test_sp_ref_q_lens_padded_row_drop():
    """Serving-oracle satellite: the verify/chunk-window contract —
    slot b's first q_lens[b] rows are a window ending at kv_lens[b]-1,
    causal within; PADDED rows (s >= q_lens[b]) clamp to the last
    valid row (their outputs are discarded by the caller — the same
    drop the paged kernel implements by scattering their KV out of
    bounds). Pinned so the sp serving path's masks land against it."""
    B, S, Hq, Hkv, T, d = 2, 4, 4, 2, 128, 32
    rng = np.random.RandomState(12)
    q = rng.randn(B, S, Hq, d).astype(np.float32) * 0.5
    # padded rows of slot 0 repeat its last valid row's QUERY, so the
    # clamp is observable as value equality (the mask is what clamps;
    # the caller discards padded outputs either way)
    q[0, 2:] = q[0, 1]
    q = jnp.asarray(q)
    k = jnp.asarray(rng.randn(B, Hkv, T, d), jnp.float32) * 0.5
    v = jnp.asarray(rng.randn(B, Hkv, T, d), jnp.float32) * 0.5
    kv_lens = jnp.asarray([30, 77], jnp.int32)
    q_lens = jnp.asarray([2, 4], jnp.int32)
    out = sp_flash_decode_ref(q, k, v, kv_lens, q_lens=q_lens)
    # valid rows: row s of slot b == a 1-row window at kv position
    # kv_lens[b] - q_lens[b] + s + 1
    for b in range(B):
        for s in range(int(q_lens[b])):
            L = int(kv_lens[b]) - int(q_lens[b]) + s + 1
            one = sp_flash_decode_ref(q[b:b + 1, s:s + 1],
                                      k[b:b + 1], v[b:b + 1], L)
            np.testing.assert_allclose(
                np.asarray(out[b, s]), np.asarray(one[0, 0]),
                atol=1e-6, rtol=1e-6, err_msg=f"slot {b} row {s}")
    # padded rows CLAMP to the last valid row — a defined value (the
    # caller discards them), never NaN/garbage
    padded = np.asarray(out[0, int(q_lens[0]):])
    assert np.isfinite(padded).all()
    np.testing.assert_allclose(
        padded, np.broadcast_to(np.asarray(out[0, int(q_lens[0]) - 1]),
                                padded.shape),
        atol=1e-6, rtol=1e-6)


def test_paged_partial_combine_vs_oracle():
    """The paged-partial kernel satellite (ISSUE 14): split a paged
    pool's logical tiles into disjoint ownership sets (the sp shard
    pattern), run flash_decode_paged_partial per 'chip', LSE-combine
    (kernels/flash_attn.lse_combine — the existing combine the sp
    serving attend feeds), and match the full-walk flash_decode_paged
    AND the extended sp_flash_decode_ref oracle."""
    from triton_dist_tpu.kernels.flash_attn import lse_combine
    from triton_dist_tpu.kernels.paged_kv import (
        flash_decode_paged, flash_decode_paged_partial)
    B, Hq, Hkv, d, page, maxp, NP = 2, 4, 2, 32, 8, 4, 17
    rng = np.random.RandomState(7)
    pk = jnp.asarray(rng.randn(NP, Hkv, page, d), jnp.float32) * 0.5
    pv = jnp.asarray(rng.randn(NP, Hkv, page, d), jnp.float32) * 0.5
    tbl = jnp.asarray(
        rng.permutation(NP - 1)[:B * maxp].reshape(B, maxp) + 1,
        jnp.int32)
    q = jnp.asarray(rng.randn(B, 1, Hq, d), jnp.float32) * 0.5
    kv_lens = jnp.asarray([13, 27], jnp.int32)
    full = flash_decode_paged(q, pk, pv, tbl, jnp.max(kv_lens),
                              kv_lens=kv_lens)
    accs, ms, ls = [], [], []
    for s in range(2):          # 2 fake chips, tiles split by parity
        own = np.broadcast_to(
            (np.arange(maxp)[None, :] % 2 == s), (B, maxp))
        acc, m, l = flash_decode_paged_partial(
            q, pk, pv, tbl, kv_lens=kv_lens,
            tile_owned=jnp.asarray(own.astype(np.int32)))
        accs.append(acc), ms.append(m), ls.append(l)
    out = lse_combine(jnp.stack(accs), jnp.stack(ms), jnp.stack(ls),
                      dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(full),
                               atol=2e-5, rtol=2e-5)
    # and against the extended oracle on the gathered cache
    from triton_dist_tpu.kernels.paged_kv import gather_pages
    kfull, vfull = gather_pages(pk, tbl), gather_pages(pv, tbl)
    ref = sp_flash_decode_ref(q, kfull, vfull, kv_lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=5e-5, rtol=1e-5)


def test_sp_flash_decode_kv_len_traced():
    """kv_len must be jit-traceable (it advances every decode step)."""
    B, S, Hq, Hkv, T, d = 1, 1, 4, 2, 256, 64
    q, k, v, ks, vs = _mk(B, S, Hq, Hkv, T, d, seed=7)
    f = jax.jit(lambda q, k, v, L: sp_flash_decode(
        q, k, v, L, mesh=mesh, combine="dist"))
    with jax.default_matmul_precision("highest"):
        for kv_len in (1, 33, 255):
            out = f(q, ks, vs, jnp.int32(kv_len))
            ref = sp_flash_decode_ref(q, k, v, kv_len)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       atol=5e-5, rtol=1e-5,
                                       err_msg=f"kv_len={kv_len}")
