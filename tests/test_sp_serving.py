"""Sequence-parallel paged decode under the scheduler (ISSUE 14 —
ROADMAP long-context item): a slot's paged KV shards along an `sp`
mesh axis (page-id space partitioned per chip, table/allocator/radix
tree host-side and layout-blind — kv_cache.PagedSlotCache SP
SHARDING), each decode tick walks only local pages through the
split-KV partial kernel (kernels/paged_kv.flash_decode_paged_partial)
and merges via the cross-chip LSE combine
(kernels/sp_flash_decode.sp_combine_partials), so max context scales
with the mesh while streams stay BITWISE equal to a single-chip
scheduler — across sampling modes, spec decode, prefix sharing,
chunked prefill, preemption, the host KV tier, and the overlap
scheduler. Plus: the long-context CAPACITY acceptance (a context one
chip's pool hard-rejects admits under sp=4), the jit-churn guard, the
capability-accurate construction refusals, and the PER-SHARD zero-leak
invariant (available + outstanding == pages_per_shard on every shard
after preemption/chaos; resident 0 at idle).

Token-stream (not logit) equality across topologies is the contract —
the LSE-combine regrouping is reduction-reordering exactly like the TP
psums, and the tiny test model keeps it far from every argmax/sample
boundary (the test_tp_serving.py rule).

Tier-1 keeps the greedy core + capacity acceptance + churn guard +
validation/allocator units (the suite sits ~845 s of the 870 s gate on
this host); the sampled/spec, chunked+overlap, preemption+host-tier
and chaos arms carry `slow` marks — `bash tools/sp_smoke.sh` is the
focused full-matrix loop.
"""

import dataclasses

import jax
import numpy as np
import pytest

from triton_dist_tpu.models import (AutoLLM, ContinuousScheduler, Engine,
                                    Request)
from triton_dist_tpu.models.config import tiny_qwen3

_SP = 4          # the sp topology under test (8 forced devices)
_MODELS = {}
_ENGINES = {}


def _model(sp):
    """sp=1 -> the plain single-chip model; sp=_SP -> the same config
    (bitwise-identical weights — random_init computes values
    mesh-independently) over a ("tp"=1, "sp"=sp) mesh with the paged
    pool's page-id space sharded over "sp"."""
    if sp not in _MODELS:
        if len(jax.devices()) < sp:
            pytest.skip(f"needs >= {sp} devices")
        cfg = tiny_qwen3(4)
        if sp == 1:
            mesh = jax.make_mesh((1,), ("tp",))
            _MODELS[sp] = (cfg, AutoLLM.from_config(cfg, mesh))
        else:
            mesh = jax.make_mesh((1, sp), ("tp", "sp"))
            _MODELS[sp] = (cfg, AutoLLM.from_config(cfg, mesh,
                                                    sp_axis="sp"))
    return _MODELS[sp]


def _engine(sp, **kw):
    key = (sp,) + tuple(sorted(kw.items()))
    if key not in _ENGINES:
        cfg, model = _model(sp)
        _ENGINES[key] = Engine(model, max_seq=64, backend="flash", **kw)
    return _ENGINES[key]


def _requests(cfg, *, shared_prefix_len=6, seed=0):
    rng = np.random.RandomState(seed)
    prefix = rng.randint(0, cfg.vocab_size,
                         size=(shared_prefix_len,)).astype(np.int32)
    spec = [(5, 5), (9, 6), (3, 4), (12, 5)]
    out = []
    for i, (L, g) in enumerate(spec):
        ids = rng.randint(0, cfg.vocab_size, size=(L,)).astype(np.int32)
        if i % 2:
            ids = np.concatenate([prefix, ids]).astype(np.int32)
        out.append(Request(rid=i, ids=ids, gen_len=g, seed=100 + i))
    return out


def _run(eng, reqs, **sk):
    sched = ContinuousScheduler(eng, batch=2, paged=True, chunk=2, **sk)
    out = sched.run([dataclasses.replace(r) for r in reqs])
    return out, sched


def _assert_same_streams(cfg, ekw, skw, label):
    reqs = _requests(cfg)
    out1, _ = _run(_engine(1, **ekw), reqs, **skw)
    outS, schedS = _run(_engine(_SP, **ekw), reqs, **skw)
    for r in reqs:
        np.testing.assert_array_equal(
            outS[r.rid], out1[r.rid],
            err_msg=f"{label}: rid={r.rid} diverged sp={_SP} vs sp=1")
    return schedS


def _assert_per_shard_conservation(sched):
    pool = sched.slots.prefix.pool
    av, outst = pool.available_by_shard, pool.outstanding_by_shard
    pps = pool.pages_per_shard
    assert all(a + o == pps for a, o in zip(av, outst)), (
        f"per-shard zero-leak violated: free {av} + outstanding "
        f"{outst} != {pps} per shard")


def test_paged_greedy_sp_equals_sp1():
    cfg, _ = _model(1)
    sched = _assert_same_streams(cfg, {}, {}, "greedy paged+prefix")
    st = sched.stats()
    assert st["sp_size"] == _SP
    assert st["hits"] > 0, "prefix cache never hit — differential vacuous"
    # the decode tick's wait is attributed to the sp-combine bucket
    assert st["device_wait_s_by_kind"]["sp_combine"] > 0
    assert len(st["sp_pages_resident"]) == _SP
    _assert_per_shard_conservation(sched)
    # per-chip throughput divides by the WHOLE mesh (tp * sp)
    assert st["serving_tok_per_s_per_chip"] == pytest.approx(
        st["serving_tok_per_s_aggregate"] / _SP, abs=2e-3)


def test_long_context_capacity_sp():
    """THE acceptance criterion: a context whose KV footprint exceeds
    one chip's paged pool — sp=1 hard-rejects it UPFRONT (host-side,
    before any device work) — admits and decodes under sp=4, with the
    stream bitwise equal to a single-chip reference on a pool big
    enough for both. Max context grew x sp."""
    cfg, _ = _model(1)
    page = 8
    chip_pages = 4 + 1                   # one chip's pool: 4 pages + 1
    long_req = Request(rid="long",
                       ids=(np.arange(40) % cfg.vocab_size
                            ).astype(np.int32),
                       gen_len=8, seed=1)
    s1 = ContinuousScheduler(_engine(1), batch=1, paged=True, chunk=2,
                             page=page, num_pages=chip_pages)
    out1 = s1.run([dataclasses.replace(long_req)])
    assert not out1.get("long", ()).__len__(), out1
    assert "long" in s1.rejected and "exceeds" in s1.rejected["long"]
    # the same per-chip pool x4 chips admits it
    s4 = ContinuousScheduler(_engine(_SP), batch=1, paged=True, chunk=2,
                             page=page, num_pages=chip_pages * _SP)
    out4 = s4.run([dataclasses.replace(long_req)])
    assert len(out4["long"]) == 8
    _assert_per_shard_conservation(s4)
    # correctness where both fit: a single-chip pool of the same TOTAL
    # size (matching NP keeps this one program family, not two)
    sb = ContinuousScheduler(_engine(1), batch=1, paged=True, chunk=2,
                             page=page, num_pages=chip_pages * _SP)
    outB = sb.run([dataclasses.replace(long_req)])
    np.testing.assert_array_equal(out4["long"], outB["long"])


def test_sp_no_new_programs_per_poll():
    """Jit-churn guard: once the sp=4 slot programs are warm, a
    steady-state burst (refill included) compiles NOTHING — the sp
    pool rides the same per-chunk-shape executables poll after poll
    (admission changes table data, never programs)."""
    import logging

    cfg, _ = _model(_SP)
    eng = _engine(_SP)
    _run(eng, _requests(cfg, seed=3))       # warm every shape

    class _H(logging.Handler):
        names: list = []

        def emit(self, record):
            msg = record.getMessage()
            if msg.startswith("Compiling "):
                self.names.append(msg.split()[1])

    h = _H()
    logger = logging.getLogger("jax._src.interpreters.pxla")
    prev = jax.config.jax_log_compiles
    jax.config.update("jax_log_compiles", True)
    logger.addHandler(h)
    try:
        _run(eng, _requests(cfg, seed=3))
    finally:
        jax.config.update("jax_log_compiles", prev)
        logger.removeHandler(h)
    assert not h.names, (
        f"steady-state sp={_SP} burst compiled fresh XLA programs "
        f"{h.names} — the sp paged path is churning executables")


def test_sp_capability_gates():
    """Satellite: every unsupported sp combination refuses at
    Engine/make_paged_slot_cache construction with a capability-named
    ValueError — never a shape error deep in jit (the PR-13 gate
    pattern)."""
    from triton_dist_tpu.models.kv_cache import PagedSlotCache
    cfg, model_sp = _model(_SP)
    # sp + comm-kernel backends: weights replicate over sp
    with pytest.raises(ValueError, match="flash"):
        Engine(model_sp, max_seq=64, backend="gemm_ar")
    # sp on contiguous slots: no pages to shard
    with pytest.raises(ValueError, match="contiguous"):
        _engine(_SP).make_slot_cache(2)
    # mesh-size-divides-page-count, at the engine AND the pool
    with pytest.raises(ValueError, match="divisible by the sp"):
        _engine(_SP).make_paged_slot_cache(1, page=8,
                                           num_pages=_SP * 7 + 1)
    mesh = model_sp.mesh
    with pytest.raises(ValueError, match="divisible by the sp"):
        PagedSlotCache.create(1, 1, 64, cfg.num_kv_heads, cfg.head_dim,
                              page=8, num_pages=_SP * 3 + 1, mesh=mesh,
                              sp_axis="sp")
    # sp + TP head-group hybrid beyond what ships
    if len(jax.devices()) >= 4:
        mesh22 = jax.make_mesh((2, 2), ("tp", "sp"))
        hybrid = AutoLLM.from_config(cfg, mesh22, sp_axis="sp")
        with pytest.raises(ValueError, match="hybrid"):
            Engine(hybrid, max_seq=64, backend="flash")


def test_sp_allocator_per_shard_unit():
    """Host-side allocator unit: the page-id space partitions per
    shard, fresh pages ROTATE across shards (consecutive logical
    tiles interleave chips), frees return to the page's own shard, and
    conservation holds per shard through arbitrary churn. The trash
    reserves shard 0's page 0."""
    from triton_dist_tpu.models.prefix_cache import RefcountedPages
    pool = RefcountedPages(4 * 8, shards=4)
    assert pool.trash == 0 and pool.shards == 4
    assert pool.pages_per_shard == 8
    gs = [pool.alloc_page() for _ in range(6)]
    # rotation: consecutive pages land on different shards
    assert len({g // 8 for g in gs[:4]}) == 4
    for g in gs[::2]:
        pool.release(g)
    av, outst = pool.available_by_shard, pool.outstanding_by_shard
    assert all(a + o == 8 for a, o in zip(av, outst)), (av, outst)
    # resident excludes the trash; frees landed on their own shards
    assert sum(pool.pages_in_use_by_shard) == pool.pages_in_use
    for g in gs[1::2]:
        pool.release(g)
    assert pool.pages_in_use_by_shard == [0, 0, 0, 0]
    assert pool.available == 4 * 8 - 1          # trash stays reserved
    # divisibility is validated at construction
    with pytest.raises(ValueError, match="divide"):
        RefcountedPages(31, shards=4)


def _dist_combine_usable():
    """Probe whether the one-sided Pallas LSE-combine kernel runs on
    this host (some jax builds carry a dma_start discharge bug that
    breaks interpret-mode comm kernels — the tier-1 seed already
    counts those failures as environmental)."""
    import jax.numpy as jnp
    from triton_dist_tpu.kernels.sp_flash_decode import sp_flash_decode
    _, model = _model(_SP)
    try:
        mesh = jax.make_mesh((_SP,), ("sp",))
        q = jnp.ones((1, 1, 4, 32), jnp.float32)
        k = jnp.ones((1, 2, 32 * _SP, 32), jnp.float32)
        np.asarray(jax.jit(lambda q, k: sp_flash_decode(
            q, k, k, 16, mesh=mesh, combine="dist"))(q, k))
        return True
    except Exception:
        return False


@pytest.mark.slow
def test_sp_dist_combine_equals_xla():
    """The paper-kernel combine in the serving tick: streams through
    sp_combine="dist" (the one-sided Pallas push+reduce kernel) must
    equal sp_combine="xla" token for token. Probe-guarded: skipped on
    hosts whose interpret mode cannot run the comm kernels."""
    if not _dist_combine_usable():
        pytest.skip("interpret-mode comm kernels unavailable on this "
                    "host (pre-existing environment limitation)")
    import dataclasses as dc
    cfg, model_sp = _model(_SP)
    model_dist = dc.replace(model_sp, sp_combine="dist")
    eng_dist = Engine(model_dist, max_seq=64, backend="flash")
    reqs = _requests(cfg)
    out_x, _ = _run(_engine(_SP), reqs)
    out_d, _ = _run(eng_dist, reqs)
    for r in reqs:
        np.testing.assert_array_equal(out_d[r.rid], out_x[r.rid],
                                      err_msg=f"rid={r.rid}")


@pytest.mark.slow
def test_sp_sampled_and_spec_equals_sp1():
    """Full-matrix arm (slow — tools/sp_smoke.sh runs it)."""
    cfg, _ = _model(1)
    _assert_same_streams(cfg, dict(sampling="top_k", temperature=0.8),
                         {}, "sampled paged sp")
    _assert_same_streams(cfg, {}, dict(spec=2), "spec=2 paged sp")


@pytest.mark.slow
def test_sp_int8_pool_equals_sp1():
    """The int8 sp composition the pool layout promises: scale planes
    shard alongside the payload over the sp axis (same page ids, same
    owners), the sp attends quantize owner-side and dequant in-kernel,
    and the boundary CoW/gather/restore move scales with payloads —
    streams bitwise sp=4 == sp=1 on the quantized pool, decode AND
    spec-verify windows."""
    import jax.numpy as jnp
    cfg, _ = _model(1)
    _assert_same_streams(cfg, dict(kv_dtype=jnp.int8), {}, "int8 sp")
    _assert_same_streams(cfg, dict(kv_dtype=jnp.int8), dict(spec=2),
                         "int8 spec=2 sp")


@pytest.mark.slow
def test_sp_chunked_prefill_and_overlap_equals_sp1():
    """Chunked prefill over the sp pool IS the blockwise ring-style
    prefill: each chunk's window attends the distributed pages through
    the same partial + cross-chip LSE combine as decode."""
    cfg, _ = _model(1)
    _assert_same_streams(cfg, {}, dict(prefill_budget=4),
                         "chunked prefill sp")
    _assert_same_streams(cfg, {}, dict(overlap=True), "overlap sp")


@pytest.mark.slow
def test_sp_preemption_host_tier_and_chaos():
    """Pool pressure on both topologies (identical host-side
    schedules), the host tier's d2h/h2d round trip over the sp pool (a
    demoted span is assembled from S per-chip page sets and scattered
    back comm-free), and forced-exhaustion chaos — with the per-shard
    zero-leak invariant checked after every arm."""
    from triton_dist_tpu.runtime.chaos import FaultInjector
    cfg, _ = _model(1)
    # ~6 usable pages: two mid-size slots fit, further
    # admissions must evict (and preempt once victims have progress)
    pool_kw = dict(num_pages=(6 + _SP) // _SP * _SP, page=8)
    s1 = _assert_same_streams(cfg, {}, pool_kw, "preemption pressure sp")
    _assert_per_shard_conservation(s1)
    tier = dict(pool_kw, host_pool_pages=64)
    s2 = _assert_same_streams(cfg, {}, tier, "host tier sp")
    _assert_per_shard_conservation(s2)
    pressure = (s2.stats()["demotions"] + s1.stats()["evictions"]
                + s1.preemptions)
    assert pressure > 0, \
        "pool pressure never materialized — differential vacuous"
    # chaos: forced PoolExhausted on admission attempts -> the preempt/
    # wait ladder runs on the sp pool; conservation must survive and
    # the cache-off idle pool must drain to resident 0 per shard
    reqs = _requests(cfg, seed=5)
    out, sched = _run(_engine(_SP), reqs, prefix_cache=False,
                      fault=FaultInjector(exhaust_admissions=(1, 3)))
    assert all(len(out[r.rid]) == r.gen_len for r in reqs)
    _assert_per_shard_conservation(sched)
    assert sched.slots.prefix.pool.pages_in_use_by_shard == [0] * _SP, \
        "sp pool not resident-0 at idle (cache-off)"
