"""Registry-driven autotuning sweep (ISSUE 16, ROADMAP item 5): the
prune -> time -> persist loop in triton_dist_tpu/tools/sweep.py plus
the tune.py hardening that carries it (shape-bucketed cache keys,
merge-on-store) and the KernelSpec `tunables` contract.

The acceptance spine is the BITWISE-IDENTITY matrix: a populated tune
cache holding a non-default surviving config must produce byte-for-
byte the same output as no cache at all — tunable axes are schedule
knobs only. The cheap arms run tier-1; the arms that execute
interpreted kernels repeatedly (the full CLI sweep of the 3-kernel
subset, the flash bitwise arms) carry `slow` — tools/tune_smoke.sh is
the focused full-matrix loop.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from triton_dist_tpu.kernels import KernelSpec, kernel_registry
from triton_dist_tpu.tools import sweep
from triton_dist_tpu.tools import tune

mesh = None


def setup_module(module):
    global mesh
    n = len(jax.devices())
    module.mesh = jax.make_mesh((n,), ("tp",))


def _store(monkeypatch, tmp_path, name="tune_cache.json"):
    """Point the sweep store (and the AutoTuner disk cache, which the
    sweep writes through) at test-private files."""
    path = str(tmp_path / name)
    monkeypatch.setenv("TDTPU_TUNE_CACHE", path)
    monkeypatch.setenv("TDTPU_AUTOTUNE_CACHE", str(tmp_path / "auto.json"))
    return path


# ---------------------------------------------------------------------------
# tune.py hardening: shape buckets + merge-on-store
# ---------------------------------------------------------------------------

def test_shape_bucket_pow2_rounding():
    assert tune.shape_bucket((5, 256)) == "8x256"
    assert tune.shape_bucket((8, 256)) == "8x256"
    assert tune.shape_bucket((9, 256)) == "16x256"
    assert tune.shape_bucket((1, 1)) == "1x1"      # n <= 1 passes through
    assert tune.shape_bucket((0, 3)) == "0x4"


def test_store_cache_merges_concurrent_writers(tmp_path):
    """_store_cache unions keys with what is already on disk instead of
    last-writer-wins: two sweep processes tuning disjoint kernels both
    land; a same-key rewrite takes the newest value."""
    path = str(tmp_path / "auto.json")
    tune._store_cache(path, {"k1": {"cfg": {"a": 1}}})
    tune._store_cache(path, {"k2": {"cfg": {"b": 2}}})
    with open(path) as f:
        disk = json.load(f)
    assert disk == {"k1": {"cfg": {"a": 1}}, "k2": {"cfg": {"b": 2}}}
    tune._store_cache(path, {"k1": {"cfg": {"a": 9}}})
    with open(path) as f:
        assert json.load(f)["k1"] == {"cfg": {"a": 9}}


def test_sweep_store_update_unions_cells(tmp_path):
    """The sweep store's writer merges at (chip, kernel, bucket) depth."""
    path = str(tmp_path / "tc.json")
    sweep.store_update(path, "cpu:x", "ka", "8x256", {"cfg": {"a": 1}})
    sweep.store_update(path, "cpu:x", "kb", "*", {"cfg": {"b": 2}})
    sweep.store_update(path, "cpu:x", "ka", "16x256", {"cfg": {"a": 3}})
    with open(path) as f:
        disk = json.load(f)
    assert disk["cpu:x"]["ka"] == {"8x256": {"cfg": {"a": 1}},
                                   "16x256": {"cfg": {"a": 3}}}
    assert disk["cpu:x"]["kb"] == {"*": {"cfg": {"b": 2}}}


def test_autotuner_bucket_shapes_shares_entries(tmp_path):
    """bucket_shapes=True keys the cache by power-of-two bucket: after
    tuning at one shape, a same-bucket shape replays the winner with NO
    new timing; default (exact) keying still re-tunes per shape."""
    calls = []

    def fn(x, scale=1):
        calls.append(x.shape)
        return x * scale

    cfgs = [{"scale": 1}, {"scale": 2}]
    t = tune.AutoTuner(fn, cfgs, name="bkt", iters=1, warmup=0,
                       cache_path=str(tmp_path / "a.json"),
                       bucket_shapes=True)
    t.pick(jnp.zeros((8, 256)))
    n_timed = len(calls)
    assert n_timed == len(cfgs)          # one timing pass
    t.pick(jnp.zeros((5, 256)))          # same bucket: replay, no calls
    assert len(calls) == n_timed
    t2 = tune.AutoTuner(fn, cfgs, name="bkt2", iters=1, warmup=0,
                        cache_path=str(tmp_path / "a.json"))
    t2.pick(jnp.zeros((8, 256)))
    t2.pick(jnp.zeros((5, 256)))         # exact keys: tuned again
    assert len(calls) == n_timed + 2 * len(cfgs)


# ---------------------------------------------------------------------------
# KernelSpec tunables contract (registration-time validation)
# ---------------------------------------------------------------------------

def test_kernelspec_rejects_malformed_tunables():
    build = lambda m: (lambda x: x, (jnp.zeros((8,)),))  # noqa: E731
    with pytest.raises(ValueError, match="dict"):
        KernelSpec("t", "tests", "compute", build, tunables=("x",))
    with pytest.raises(ValueError, match="empty"):
        KernelSpec("t", "tests", "compute", build, tunables=({},))
    with pytest.raises(ValueError, match="key"):
        KernelSpec("t", "tests", "compute", build,
                   tunables=({"a": 1}, {"b": 2}))
    with pytest.raises(ValueError, match="variants"):
        KernelSpec("t", "tests", "compute", build, variants=(build,))
    # well-formed: uniform keys, variants riding a declared space
    KernelSpec("t", "tests", "compute", build,
               tunables=({"a": 1}, {"a": 2}), variants=(build,))


def test_registry_declares_schedule_spaces():
    """The registry stays at its full size and the tuned kernels carry
    uniform-key spaces; fp-order-changing knobs stay out by contract
    (flash block_t / ep_fused block_i are never tunable axes)."""
    reg = kernel_registry()
    assert len(reg) == 31
    tuned = {n for n, s in reg.items() if s.tunables}
    assert {"flash_decode", "flash_decode_paged",
            "flash_decode_paged_partial", "grouped_gemm", "ag_gemm",
            "gemm_rs", "gemm_ar", "ag_group_gemm", "moe_reduce_rs",
            "ep_fused"} <= tuned
    for n in tuned:
        keys = {frozenset(c) for c in reg[n].tunables}
        assert len(keys) == 1, n
        assert "block_t" not in next(iter(keys)), n
        assert "block_i" not in next(iter(keys)), n


# ---------------------------------------------------------------------------
# static pruning (the tdcheck contracts checker, reused not forked)
# ---------------------------------------------------------------------------

def test_prune_drops_indivisible_stream_block():
    """flash_decode_paged's canonical build has B = 4 slots:
    block_w=8 cannot divide them and must be pruned statically, with
    the reason recorded; the legal grouping survives intact."""
    spec = kernel_registry()["flash_decode_paged"]
    survivors, rejected = sweep.prune_space(spec, mesh)
    assert survivors == [{"block_w": 1}, {"block_w": 2}, {"block_w": 4}]
    assert [cfg for cfg, _ in rejected] == [{"block_w": 8}]
    assert "block_w=8" in rejected[0][1]


def test_estimator_counts_paged_walk_scratch():
    """The walk's pools stay in HBM and its double K and V block
    buffers are VMEM scratch, not BlockSpec blocks: the shared
    estimator (the pruner's and tdcheck's) must count them, 2 planes x
    2 halves x W slots x Hkv heads x 128 positions x d, and grow with
    W."""
    from triton_dist_tpu.analysis.contracts import estimate_vmem
    from triton_dist_tpu.kernels.paged_kv import flash_decode_paged
    B, Hq, Hkv, d, page, maxp = 8, 4, 2, 128, 16, 16
    NP = B * maxp
    q = jnp.zeros((B, 1, Hq, d), jnp.bfloat16)
    pages = jnp.zeros((NP, Hkv, page, d), jnp.bfloat16)
    table = jnp.arange(NP, dtype=jnp.int32).reshape(B, maxp)
    lens = jnp.full((B,), 40, jnp.int32)

    def vmem(w):
        return estimate_vmem(
            lambda *a: flash_decode_paged(*a[:4], None, kv_lens=a[4],
                                          block_w=w),
            (q, pages, pages, table, lens))

    buffers = lambda w: 2 * 2 * w * Hkv * 128 * d * 2
    assert buffers(8) <= vmem(8) < 2 * buffers(8)
    assert vmem(8) - vmem(2) >= buffers(8) - buffers(2)


def test_prune_rejects_all_pruned_space():
    """A tunables space whose EVERY config fails the pruner is a typo'd
    registration: prune_space raises instead of silently sweeping
    nothing (and the CLI surfaces it as an error line)."""
    base = kernel_registry()["flash_decode_paged"]
    bad = KernelSpec(base.name, base.module, base.kind, base.build,
                     tunables=({"block_w": 7},))
    with pytest.raises(ValueError, match="every config"):
        sweep.prune_space(bad, mesh)


def test_prune_rejects_overbudget_vmem_config():
    """The pruner prices VMEM through the SAME estimator the checker
    uses (analysis.contracts.estimate_vmem): a config that blows the
    budget at the canonical shapes is rejected before any timing."""
    from jax.experimental import pallas as pl

    def build(m):
        def kern(x_ref, o_ref):
            o_ref[...] = x_ref[...]

        def f(x):
            from triton_dist_tpu.tools.sweep import resolve_config
            blk = resolve_config("evil_sweep").get("blk", 128)
            return pl.pallas_call(
                kern, grid=(4,),
                in_specs=[pl.BlockSpec((blk, 2048), lambda i: (0, 0))],
                out_specs=pl.BlockSpec((blk, 2048), lambda i: (0, 0)),
                out_shape=jax.ShapeDtypeStruct((2048, 2048), jnp.float32),
                interpret=True)(x)

        return f, (jnp.zeros((2048, 2048), jnp.float32),)

    spec = KernelSpec("evil_sweep", "tests", "compute", build,
                      tunables=({"blk": 128}, {"blk": 2048}))
    survivors, rejected = sweep.prune_space(spec, mesh)
    assert survivors == [{"blk": 128}]
    assert rejected[0][0] == {"blk": 2048}
    assert "VMEM" in rejected[0][1]


# ---------------------------------------------------------------------------
# persist + reload per (kernel, shape-bucket, chip)
# ---------------------------------------------------------------------------

def test_sweep_kernel_persists_and_reloads(monkeypatch, tmp_path):
    spec = kernel_registry()["grouped_gemm"]
    path = _store(monkeypatch, tmp_path)
    res = sweep.sweep_kernel(spec, mesh, iters=1, warmup=1,
                             store_path=path)
    # canonical C=64 bucket + the declared C=256 variant bucket
    assert [r["bucket"] for r in res] == ["64x128", "256x128"]
    assert all(not r["cached"] for r in res)
    chip = tune._device_tag()
    with open(path) as f:
        disk = json.load(f)
    cells = disk[chip]["grouped_gemm"]
    assert set(cells) == {"64x128", "256x128"}
    for cell in cells.values():
        assert cell["cfg"] in list(spec.tunables)
        assert cell["space"] == len(spec.tunables)
    # second sweep: both buckets replay from the store, nothing re-run
    res2 = sweep.sweep_kernel(spec, mesh, iters=1, warmup=1,
                              store_path=path)
    assert all(r["cached"] for r in res2)
    assert [r["cfg"] for r in res2] == [r["cfg"] for r in res]
    # and the consumer-facing lookup resolves per bucket
    assert sweep.tuned_choice("grouped_gemm", (64, 128), path=path) \
        == res[0]["cfg"]
    assert sweep.tuned_choice("grouped_gemm", (200, 128), path=path) \
        == res[1]["cfg"]                  # 200 rounds up to the 256 bucket


def test_tuned_choice_buckets_and_fallback(tmp_path):
    path = str(tmp_path / "tc.json")
    chip = tune._device_tag()
    sweep.store_update(path, chip, "k", "8x256", {"cfg": {"a": 1}})
    assert sweep.tuned_choice("k", (5, 256), path=path) == {"a": 1}
    # single swept bucket: any dims fall back to it (schedule-only cfg)
    assert sweep.tuned_choice("k", (512, 512), path=path) == {"a": 1}
    sweep.store_update(path, chip, "k", "16x256", {"cfg": {"a": 2}})
    # two buckets: exact match or nothing
    assert sweep.tuned_choice("k", (16, 256), path=path) == {"a": 2}
    assert sweep.tuned_choice("k", (512, 512), path=path) is None
    # wrong chip tag: invisible
    sweep.store_update(path, "tpu:v9", "k2", "*", {"cfg": {"z": 9}})
    assert sweep.tuned_choice("k2", path=path) is None


def test_resolve_config_precedence(monkeypatch, tmp_path):
    """contextual profile > tune cache > {} — and the in-process
    override always wins while installed."""
    path = _store(monkeypatch, tmp_path)
    assert sweep.resolve_config("flash_decode", (4, 256)) == {}
    sweep.store_update(path, tune._device_tag(), "flash_decode",
                       "4x256", {"cfg": {"block_x": 128}})
    assert sweep.resolve_config("flash_decode", (4, 256)) \
        == {"block_x": 128}
    with tune.contextual_override("flash_decode", {"block_x": 32}):
        assert sweep.resolve_config("flash_decode", (4, 256)) \
            == {"block_x": 32}
    assert sweep.resolve_config("flash_decode", (4, 256)) \
        == {"block_x": 128}


# ---------------------------------------------------------------------------
# bitwise identity: tuned-config paths emit the same bytes (acceptance)
# ---------------------------------------------------------------------------

def _bits(x):
    return np.asarray(x).tobytes()


def test_grouped_gemm_bitwise_identical_under_cache(monkeypatch,
                                                    tmp_path):
    """A populated store holding a NON-default surviving config changes
    only the schedule: grouped_gemm's output bytes are identical with
    and without the cache."""
    from triton_dist_tpu.kernels import grouped_gemm
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(2, 64, 128), jnp.float32)
    w = jnp.asarray(rng.randn(2, 128, 128), jnp.float32)
    path = _store(monkeypatch, tmp_path)
    base = _bits(grouped_gemm(x, w))
    sweep.store_update(path, tune._device_tag(), "grouped_gemm",
                       "64x128",
                       {"cfg": {"block_c": 128, "block_f": 256}})
    assert _bits(grouped_gemm(x, w)) == base
    # explicit args still beat the cache — and stay bitwise equal too
    assert _bits(grouped_gemm(x, w, block_c=8, block_f=128)) == base


@pytest.mark.slow
def test_flash_decode_bitwise_identical_under_cache(monkeypatch,
                                                    tmp_path):
    """block_x regroups KV streams across grid steps only (each
    stream's online-softmax order is untouched): tuned block_x=32 must
    be byte-identical to the hand-picked 64."""
    from triton_dist_tpu.kernels import flash_decode
    rng = np.random.RandomState(4)
    B, Hq, Hkv, T, d = 2, 4, 2, 256, 128
    q = jnp.asarray(rng.randn(B, 1, Hq, d), jnp.float32)
    k = jnp.asarray(rng.randn(B, Hkv, T, d), jnp.float32)
    v = jnp.asarray(rng.randn(B, Hkv, T, d), jnp.float32)
    path = _store(monkeypatch, tmp_path)
    base = _bits(flash_decode(q, k, v, jnp.int32(T)))
    sweep.store_update(path, tune._device_tag(), "flash_decode",
                       "4x256", {"cfg": {"block_x": 32}})
    assert _bits(flash_decode(q, k, v, jnp.int32(T))) == base


@pytest.mark.slow
def test_flash_decode_paged_bitwise_identical_under_cache(monkeypatch,
                                                          tmp_path):
    """block_w regroups page-walk streams per grid step: tuned
    block_w=2 must match the default divisor pick (4) byte-for-byte."""
    from triton_dist_tpu.kernels.paged_kv import flash_decode_paged
    rng = np.random.RandomState(5)
    B, Hq, Hkv, d, page, maxp = 4, 4, 2, 128, 128, 4
    NP = B * maxp
    q = jnp.asarray(rng.randn(B, 1, Hq, d), jnp.float32)
    pages = jnp.asarray(rng.randn(NP, Hkv, page, d), jnp.float32)
    table = jnp.arange(NP, dtype=jnp.int32).reshape(B, maxp)
    kv_lens = jnp.asarray([page * maxp, page, 3, 2 * page + 5],
                          jnp.int32)
    path = _store(monkeypatch, tmp_path)
    base = _bits(flash_decode_paged(q, pages, pages, table, None,
                                    kv_lens=kv_lens))
    sweep.store_update(path, tune._device_tag(), "flash_decode_paged",
                       tune.shape_bucket((B * Hkv, B * Hq, NP * page)),
                       {"cfg": {"block_w": 2}})
    assert _bits(flash_decode_paged(q, pages, pages, table, None,
                                    kv_lens=kv_lens)) == base
    # an indivisible EXPLICIT block_w is a loud error, never a silent
    # fallback
    with pytest.raises(ValueError, match="block_w=3"):
        flash_decode_paged(q, pages, pages, table, None,
                           kv_lens=kv_lens, block_w=3)


def test_paged_tuned_block_w_reclamps_at_foreign_shape(monkeypatch,
                                                       tmp_path):
    """A tune-cache block_w that does not divide this call's slots B
    (single-bucket fallback from a sweep at another batch) must
    re-clamp to the divisor ladder, not raise at serving time — only an
    EXPLICIT indivisible block_w is an error. Exercised at B=2, Hkv=2
    against a cached winner of 8."""
    from triton_dist_tpu.kernels.paged_kv import flash_decode_paged
    rng = np.random.RandomState(6)
    B, Hq, Hkv, d, page, maxp = 2, 4, 2, 128, 128, 2
    NP = B * maxp
    q = jnp.asarray(rng.randn(B, 1, Hq, d), jnp.float32)
    pages = jnp.asarray(rng.randn(NP, Hkv, page, d), jnp.float32)
    table = jnp.arange(NP, dtype=jnp.int32).reshape(B, maxp)
    kv_lens = jnp.asarray([page * maxp, page + 1], jnp.int32)
    path = _store(monkeypatch, tmp_path)
    base = _bits(flash_decode_paged(q, pages, pages, table, None,
                                    kv_lens=kv_lens))
    # sole bucket in the store, swept at a shape where block_w=8 was
    # legal: tuned_choice's cross-bucket fallback serves it here too
    sweep.store_update(path, tune._device_tag(), "flash_decode_paged",
                       tune.shape_bucket((16, 32, 16384)),
                       {"cfg": {"block_w": 8}})
    assert _bits(flash_decode_paged(q, pages, pages, table, None,
                                    kv_lens=kv_lens)) == base


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def test_cli_dry_run_enumerates_every_kernel(monkeypatch, tmp_path,
                                             capsys):
    """--dry-run walks the WHOLE registry: every kernel prints exactly
    one status line (a prune summary, 'no tunables', or a min-devices
    skip), nothing is stored, and flash_decode_paged shows its
    block_w=8 rejection."""
    path = _store(monkeypatch, tmp_path)
    assert sweep.main(["--dry-run"]) == 0
    out = capsys.readouterr().out
    lines = [ln for ln in out.splitlines() if ln and not
             ln.startswith(" ")]
    assert len(lines) == len(kernel_registry()) == 31
    paged = [ln for ln in lines if ln.startswith("flash_decode_paged ")]
    assert paged and "surviving= 3" in paged[0]
    assert "prune {\"block_w\": 8}" in out
    assert not os.path.exists(path)      # dry: nothing persisted


def test_cli_rejects_unknown_kernel():
    with pytest.raises(SystemExit):
        sweep.main(["--kernels", "definitely_not_a_kernel",
                    "--dry-run"])


@pytest.mark.slow
def test_cli_sweeps_subset_and_persists(monkeypatch, tmp_path, capsys):
    """The bounded smoke arm tools/perf_gate.sh runs: sweep the
    3-kernel CPU-runnable subset end to end (prune -> time -> persist)
    and find every winner in the store."""
    path = _store(monkeypatch, tmp_path)
    assert sweep.main(["--kernels",
                       "flash_decode,flash_decode_paged,grouped_gemm",
                       "--iters", "1", "--warmup", "1",
                       "--store", path]) == 0
    out = capsys.readouterr().out
    assert "bucket" in out
    with open(path) as f:
        disk = json.load(f)
    chip = tune._device_tag()
    assert {"flash_decode", "flash_decode_paged", "grouped_gemm"} \
        <= set(disk[chip])
    for kern, cells in disk[chip].items():
        for cell in cells.values():
            assert cell["cfg"], (kern, cell)
