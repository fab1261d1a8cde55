"""TP-sharded paged serving (ROADMAP open item 1): ONE
ContinuousScheduler drives a TP=N mesh over the head-sharded paged
pool (kv_cache.PagedSlotCache TP SHARDING + the shard_map paged
attends of layers/tp_attn.py), and the streams must be BITWISE
identical to the same scheduler on a single chip — across sampling
modes, spec decode, prefix sharing, chunked prefill, preemption, the
host KV tier, and the overlap scheduler. Plus: the jit-churn guard
(a TP mesh compiles no extra programs per poll), the GQA/divisibility
validation, and the comm-backend proof (the decode slot path routes
through the gemm_ar TP backend — comm-kernel dispatch counter > 0).

Token-stream (not logit) equality across topologies is the contract:
per-head attention math is reduction-free across chips, and the tiny
test model keeps the TP psum reorderings far from every argmax/sample
boundary — the same robustness the backend-vs-oracle differentials
(test_e2e_inference.py) have always relied on.
"""

import dataclasses

import jax
import numpy as np
import pytest

from triton_dist_tpu.models import (AutoLLM, ContinuousScheduler, Engine,
                                    Request)
from triton_dist_tpu.models.config import tiny_qwen3

_MODELS = {}
_TP = 4          # the multi-chip topology under test (8 forced devices)


def _model(n):
    """One model per TP size, shared across tests. tiny_qwen3(_TP)
    everywhere: the SAME config (so weights are bitwise identical —
    random_init computes values mesh-independently) laid out over a
    1-chip or an n-chip mesh."""
    if n not in _MODELS:
        if len(jax.devices()) < n:
            pytest.skip(f"needs >= {n} devices")
        mesh = jax.make_mesh((n,), ("tp",))
        cfg = tiny_qwen3(_TP)
        _MODELS[n] = (cfg, AutoLLM.from_config(cfg, mesh))
    return _MODELS[n]


_ENGINES = {}


def _engine(n, **kw):
    key = (n,) + tuple(sorted(kw.items()))
    if key not in _ENGINES:
        cfg, model = _model(n)
        _ENGINES[key] = Engine(model, max_seq=64, **kw)
    return _ENGINES[key]


def _requests(cfg, *, shared_prefix_len=6, seed=0):
    """Mixed prompts, odd rids sharing a prefix (the prefix-cache
    case); 5 requests through small batches force mid-stream refill."""
    rng = np.random.RandomState(seed)
    prefix = rng.randint(0, cfg.vocab_size,
                         size=(shared_prefix_len,)).astype(np.int32)
    spec = [(5, 6), (9, 8), (3, 4), (12, 7), (7, 5)]
    out = []
    for i, (L, g) in enumerate(spec):
        ids = rng.randint(0, cfg.vocab_size, size=(L,)).astype(np.int32)
        if i % 2:
            ids = np.concatenate([prefix, ids]).astype(np.int32)
        out.append(Request(rid=i, ids=ids, gen_len=g, seed=100 + i))
    return out


def _run(eng, reqs, **sk):
    sched = ContinuousScheduler(eng, batch=3, paged=True, chunk=2, **sk)
    out = sched.run([dataclasses.replace(r) for r in reqs])
    return out, sched


def _assert_same_streams(cfg, ekw, skw, label):
    """The differential: identical request set through a TP=1 and a
    TP=_TP scheduler; every stream must match token for token."""
    reqs = _requests(cfg)
    out1, _ = _run(_engine(1, **ekw), reqs, **skw)
    outN, schedN = _run(_engine(_TP, **ekw), reqs, **skw)
    for r in reqs:
        np.testing.assert_array_equal(
            outN[r.rid], out1[r.rid],
            err_msg=f"{label}: rid={r.rid} diverged TP={_TP} vs TP=1")
    return schedN


def test_paged_greedy_tp_equals_tp1():
    cfg, _ = _model(1)
    sched = _assert_same_streams(cfg, dict(backend="flash"), {},
                                 "greedy paged+prefix")
    st = sched.stats()
    assert st["tp_size"] == _TP
    assert st["hits"] > 0, "prefix cache never hit — differential vacuous"
    assert st["serving_tok_per_s_aggregate"] > 0
    # both gauges are rounded to 3 decimals at snapshot time
    assert st["serving_tok_per_s_per_chip"] == pytest.approx(
        st["serving_tok_per_s_aggregate"] / _TP, abs=2e-3)


@pytest.mark.slow
def test_paged_sampled_and_spec_tp_equals_tp1():
    """Full-matrix arm (slow: tier-1's 870 s budget keeps the greedy
    core + churn guard; `bash tools/tp_smoke.sh` runs the whole
    matrix)."""
    cfg, _ = _model(1)
    _assert_same_streams(
        cfg, dict(backend="flash", sampling="top_k", temperature=0.8),
        {}, "sampled paged")
    _assert_same_streams(cfg, dict(backend="flash"), dict(spec=2),
                         "spec=2 paged")


@pytest.mark.slow
def test_paged_chunked_prefill_and_overlap_tp_equals_tp1():
    cfg, _ = _model(1)
    _assert_same_streams(cfg, dict(backend="flash"),
                         dict(prefill_budget=4), "chunked prefill")
    _assert_same_streams(cfg, dict(backend="flash"), dict(overlap=True),
                         "overlap")


@pytest.mark.slow
def test_paged_preemption_and_host_tier_tp_equals_tp1():
    """Pool pressure on BOTH topologies: a pool too small for the
    working set forces eviction + preemption (identical schedules —
    the policy is host-side and layout-oblivious), and with
    host_pool_pages the evicted spans take the d2h/h2d round trip on
    the sharded pool."""
    cfg, _ = _model(1)
    # ~9 usable pages: two mid-size slots fit, the third
    # admission must evict (and preempt once victims have progress)
    pool_kw = dict(num_pages=9 + 1, page=8)
    s1 = _assert_same_streams(cfg, dict(backend="flash"), pool_kw,
                              "preemption pressure")
    tier = dict(pool_kw, host_pool_pages=64)
    sched = _assert_same_streams(cfg, dict(backend="flash"), tier,
                                 "host tier")
    pressure = (sched.stats()["demotions"] + s1.stats()["evictions"]
                + s1.preemptions)
    assert pressure > 0, \
        "pool pressure never materialized — differential vacuous"


def test_tp_no_new_programs_per_poll():
    """Jit-churn guard: once the TP=N slot programs are warm, a
    steady-state serving burst (refill included) compiles NOTHING —
    the sharded pool rides the same per-chunk-shape executables as the
    single-chip loop (admission changes data, never programs)."""
    import logging

    cfg, _ = _model(_TP)
    eng = _engine(_TP, backend="flash")
    # warm every program shape this burst will use
    _run(eng, _requests(cfg, seed=3))

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
        f"steady-state TP={_TP} burst compiled fresh XLA programs "
        f"{h.names} — the sharded paged path is churning executables")


def test_kv_head_divisibility_validated():
    """Satellite: a mesh that does not divide n_kv_heads raises a
    CLEAR ValueError at pool creation — at Engine.make_paged_slot_cache
    and at PagedSlotCache.create — instead of a shard_map shape error
    deep inside compile. The message names the GQA replication factor
    explicitly (query-side replication never relaxes the KV split)."""
    from triton_dist_tpu.models.kv_cache import PagedSlotCache
    cfg, model = _model(_TP)
    bad_cfg = dataclasses.replace(cfg, num_kv_heads=_TP + 2)
    bad_model = dataclasses.replace(model, config=bad_cfg)
    eng = Engine(bad_model, max_seq=64, backend="flash")
    with pytest.raises(ValueError, match="GQA"):
        eng.make_paged_slot_cache(2)
    with pytest.raises(ValueError, match="divisible"):
        PagedSlotCache.create(1, 2, 64, _TP + 2, cfg.head_dim, page=16,
                              num_pages=32, mesh=model.mesh)


def _comm_kernels_usable():
    """Probe whether the Pallas-interpreted comm kernels run on this
    host (some jax builds carry a dma_start discharge bug that breaks
    them under interpret mode — the tier-1 seed on such hosts already
    counts those failures as environmental)."""
    import jax.numpy as jnp
    from triton_dist_tpu.kernels import (create_gemm_ar_context,
                                         gemm_allreduce)
    cfg, model = _model(_TP)
    try:
        a = jnp.ones((2, 8 * _TP), jnp.float32)
        b = jnp.ones((8 * _TP, 16), jnp.float32)
        ctx = create_gemm_ar_context(model.mesh, "tp")
        np.asarray(jax.jit(lambda a, b: gemm_allreduce(a, b, ctx))(a, b))
        return True
    except Exception:
        return False


def test_paged_gemm_ar_backend_dispatches_comm_kernels():
    """The tentpole's proof obligation: the paged decode slot path on
    a TP mesh demonstrably executes the gemm_ar TP backend — the
    fused GEMM+AR comm kernel of the paper — with streams equal to the
    oracle backend. Asserts the per-dispatch comm counter moved AND
    the kernel-build counter saw gemm_allreduce traced."""
    if not _comm_kernels_usable():
        pytest.skip("interpret-mode comm kernels unavailable on this "
                    "host (pre-existing environment limitation)")
    from triton_dist_tpu.runtime.telemetry import default_registry
    cfg, _ = _model(_TP)
    reqs = _requests(cfg)[:3]
    out_ref, _ = _run(_engine(_TP, backend="xla"), reqs)
    reg = default_registry()
    disp0 = reg.counter("comm_kernel_dispatches").value
    tr0 = reg.counter("comm_kernel_traces").value
    out, _ = _run(_engine(_TP, backend="gemm_ar"), reqs)
    assert reg.counter("comm_kernel_dispatches").value > disp0, \
        "no slot dispatch routed through the comm backend"
    assert reg.counter("comm_kernel_traces").value > tr0, \
        "gemm_ar backend never traced a comm kernel"
    for r in reqs:
        np.testing.assert_array_equal(out[r.rid], out_ref[r.rid],
                                      err_msg=f"rid={r.rid}")


def test_every_chip_reads_only_its_own_head_shard():
    """One decode tick of the paged attend on the TP=4 mesh. A chip's
    shard of a plane is [NP, Hkv/4, page, d]: its own kv heads of every
    page. With every OTHER chip's heads of the pool turned to NaN, the
    query heads that chip computes must come out bitwise what the clean
    pool gives them (and the poisoned chips' NaN): nothing crosses."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from triton_dist_tpu.layers import TP_Attn, precompute_rope
    from triton_dist_tpu.models.kv_cache import PagedSlotCache
    if len(jax.devices()) < _TP:
        pytest.skip(f"needs >= {_TP} devices")
    mesh = jax.make_mesh((_TP,), ("tp",))
    B, Hq, Hkv, hd, D, page, T = 3, 8, 4, 32, 64, 8, 32
    rng = np.random.RandomState(35)
    w = lambda *s: rng.randn(*s).astype(np.float32) * 0.1
    attn = TP_Attn.init(w(D, Hq * hd), w(D, Hkv * hd), w(D, Hkv * hd),
                        w(Hq * hd, D), mesh=mesh, n_heads=Hq,
                        n_kv_heads=Hkv, head_dim=hd)
    cos, sin = precompute_rope(hd, T)
    pc = PagedSlotCache.create(1, B, T, Hkv, hd, page=page,
                               num_pages=B * (T // page) + 1, mesh=mesh,
                               dtype=jnp.float32)
    NP = pc.num_pages
    assert pc.pages_k[0].sharding.shard_shape(pc.pages_k[0].shape) == (
        NP, Hkv // _TP, page, hd)
    assert pc.table.shape == (B, T // page)
    shd = NamedSharding(mesh, P(None, "tp", None, None))
    pk, pv = (rng.randn(NP, Hkv, page, hd).astype(np.float32)
              for _ in range(2))
    table = jnp.asarray(
        1 + rng.permutation(NP - 1).reshape(B, T // page), jnp.int32)
    pos = jnp.asarray([5, 17, 30], jnp.int32)
    qkv = jax.device_put(
        jnp.asarray(rng.randn(B, (Hq + 2 * Hkv) * hd), jnp.float32),
        NamedSharding(mesh, P(None, "tp")))

    @jax.jit
    def tick(a, qkv, k, v):
        o, kv = a._attend_paged_slots(qkv, cos, sin, B, (k, v), table,
                                      pos, "flash")
        return o, kv[0]

    def run(k, v):
        o, k2 = tick(attn, qkv, jax.device_put(jnp.asarray(k), shd),
                     jax.device_put(jnp.asarray(v), shd))
        assert k2.sharding.is_equivalent_to(shd, 4)
        return np.asarray(o).reshape(B, _TP, -1)    # a chip's columns

    clean = run(pk, pv)
    assert np.isfinite(clean).all()
    hkv = Hkv // _TP
    for chip in range(_TP):
        own = slice(chip * hkv, (chip + 1) * hkv)
        k, v = np.full_like(pk, np.nan), np.full_like(pv, np.nan)
        k[:, own], v[:, own] = pk[:, own], pv[:, own]
        got = run(k, v)
        np.testing.assert_array_equal(got[:, chip], clean[:, chip])
        assert np.isnan(np.delete(got, chip, axis=1)).all()


@pytest.mark.parametrize("tp,heads,want", [(1, 8, 32768), (_TP, 8, 8192),
                                           (1, 10, 40960)])
def test_kv_page_copy_bytes_at_the_cells_shapes(tp, heads, want):
    """What one K-plane copy of the decode walk moves on a chip, at
    the three cells' layouts (page 16, d 128, bf16): Qwen3-1.7B's 8
    heads on one chip, 2 of 8 on a TP=4 chip, Phi-4's 10 paired heads."""
    import jax.numpy as jnp
    from triton_dist_tpu.models.kv_cache import PagedSlotCache
    if len(jax.devices()) < tp:
        pytest.skip(f"needs >= {tp} devices")
    mesh = jax.make_mesh((tp,), ("tp",))
    pc = PagedSlotCache.create(1, 2, 64, heads, 128, page=16,
                               num_pages=9, mesh=mesh,
                               dtype=jnp.bfloat16)
    assert pc.page_copy_bytes == want


def test_kv_page_copy_bytes_is_in_stats_and_the_registry():
    cfg, _ = _model(_TP)
    sched = ContinuousScheduler(_engine(_TP, backend="flash"), batch=2,
                                paged=True, chunk=2, page=8)
    want = (cfg.num_kv_heads // _TP) * 8 * cfg.head_dim \
        * np.dtype(cfg.jax_dtype).itemsize
    assert sched.stats()["kv_page_copy_bytes"] == want
    from triton_dist_tpu.runtime.telemetry import prometheus_text
    assert f"tdtpu_kv_page_copy_bytes {want:g}\n" in \
        prometheus_text(sched.slots.tele.registry) + "\n"
