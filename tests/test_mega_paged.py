"""Paged megakernel serving differentials (ISSUE 12 / ROADMAP item 5):
MegaPagedDecodeLayer — one decode layer as ONE Pallas kernel over the
paged serving pool — against the per-op paged machinery it fuses, at
three altitudes:

  - KERNEL: the fused layer vs a jnp oracle (mega_paged_decode_layer_
    ref) AND vs the per-op composition (scatter + flash_decode_paged +
    jnp MLP) — per-slot kv_lens masking, trash-page write-sink safety
    for retired slots, int8 scale-plane dequant exactness (the oracle
    style of tests/test_paged_kv.py);
  - PROGRAM: the fused tick traces exactly num_layers pallas_call
    equations and FEWER device ops per poll than the per-op paged
    scan — the dispatch-count delta that is the measured win (the
    jit/dispatch churn-guard pattern, applied to the traced program);
  - SERVING: ContinuousScheduler(paged=True) streams on
    backend='mega' match backend='flash' greedy streams (bitwise
    where fusion order permits; otherwise the teacher-forced
    logit-margin oracle per the tests/test_mega.py convention),
    overlap on == off bitwise, prefix cache shared.

Heavy matrix arms (int8 e2e, chunked-prefill fallback, preemption)
carry `slow` marks per the tier-1 budget note (~828 s of the 870 s
gate); `tools/mega_smoke.sh` is the focused full-matrix loop.
"""

import functools

import jax
import jax.extend.core
import jax.numpy as jnp
import numpy as np
import pytest

from triton_dist_tpu.mega import (MegaPagedDecodeLayer,
                                  mega_paged_decode_layer_ref)


# ---------------------------------------------------------------------------
# kernel-level fixtures
# ---------------------------------------------------------------------------

_GEO = dict(B=3, D=256, Hq=4, Hkv=2, hd=64, F=512, page=8, maxp=6,
            NP=40)


def _mk_case(pos, seed=0, dtype=jnp.float32, quant=False):
    """One paged layer case: weights, per-slot rope rows, a pool whose
    table maps 2 distinct tiles per stream (rest trash-padded), random
    resident KV."""
    B, D, Hq, Hkv, hd, F = (_GEO["B"], _GEO["D"], _GEO["Hq"],
                            _GEO["Hkv"], _GEO["hd"], _GEO["F"])
    page, maxp, NP = _GEO["page"], _GEO["maxp"], _GEO["NP"]
    X = B * Hkv
    rng = np.random.RandomState(seed)
    sc = 0.3 / np.sqrt(D)
    w = {
        "w_ln1": jnp.asarray(1 + 0.1 * rng.randn(1, D), jnp.float32),
        "w_qkv": jnp.asarray(rng.randn(D, (Hq + 2 * Hkv) * hd) * sc,
                             jnp.float32),
        "q_norm": jnp.asarray(1 + 0.1 * rng.randn(1, hd), jnp.float32),
        "k_norm": jnp.asarray(1 + 0.1 * rng.randn(1, hd), jnp.float32),
        "w_o": jnp.asarray(rng.randn(Hq * hd, D) * sc, jnp.float32),
        "w_ln2": jnp.asarray(1 + 0.1 * rng.randn(1, D), jnp.float32),
        "w_gu": jnp.asarray(rng.randn(D, 2 * F) * sc, jnp.float32),
        "w_d": jnp.asarray(rng.randn(F, D) * (0.3 / np.sqrt(F)),
                           jnp.float32),
    }
    pos = np.asarray(pos, np.int32)
    assert pos.shape == (B,)
    inv = 1.0 / (1e6 ** (np.arange(0, hd, 2) / hd))
    w["cos_row"] = jnp.asarray(np.cos(pos[:, None] * inv[None]),
                               jnp.float32)
    w["sin_row"] = jnp.asarray(np.sin(pos[:, None] * inv[None]),
                               jnp.float32)
    x = jnp.asarray(rng.randn(B, D), jnp.float32) * 0.3
    if quant:
        pk = jnp.asarray(
            rng.randint(-127, 128, size=(NP, 1, page, hd)), jnp.int8)
        pv = jnp.asarray(
            rng.randint(-127, 128, size=(NP, 1, page, hd)), jnp.int8)
        sk = jnp.asarray(0.01 + 0.01 * rng.rand(NP, 1, page),
                         jnp.float32)
        sv = jnp.asarray(0.01 + 0.01 * rng.rand(NP, 1, page),
                         jnp.float32)
        scales = (sk, sv)
    else:
        pk = jnp.asarray(rng.randn(NP, 1, page, hd), dtype) * 0.3
        pv = jnp.asarray(rng.randn(NP, 1, page, hd), dtype) * 0.3
        scales = ()
    table = np.zeros((X, maxp), np.int32)   # trash-padded (page 0)
    nxt = 1
    for s_ in range(X):
        for t in range(2):
            table[s_, t] = nxt
            nxt += 1
    layer = MegaPagedDecodeLayer(
        d_model=D, n_heads=Hq, n_kv_heads=Hkv, head_dim=hd, ffn=F,
        page=page, maxp=maxp, block_n=128)
    return layer, x, jnp.asarray(pos), w, pk, pv, jnp.asarray(table), \
        scales


def _run_pair(layer, x, pos, w, pk, pv, table, scales):
    got = jax.jit(lambda *a: layer(*a))(x, pos, w, pk, pv, table,
                                        *scales)
    ref = mega_paged_decode_layer_ref(
        x, pos, w, pk, pv, table, *scales, n_heads=layer.n_heads,
        n_kv_heads=layer.n_kv_heads, head_dim=layer.head_dim)
    return got, ref


# ---------------------------------------------------------------------------
# kernel-level differentials
# ---------------------------------------------------------------------------

@pytest.mark.slow  # tier-1's 870 s budget — tools/mega_smoke.sh runs
# the full kernel-oracle matrix; tier-1 keeps the behavioral guards
# (trash-page sink, dispatch-count trace, capability errors).
def test_mega_paged_layer_vs_oracle_per_slot_lens():
    """Per-slot kv_lens: slots at pos 0, mid-page and page-crossing
    positions share ONE launch; each must mask to its own length (the
    oracle masks col <= pos[b] per slot)."""
    case = _mk_case(pos=[5, 13, 0], seed=1)
    got, ref = _run_pair(*case)
    # bf16 weight tiles inside the kernel vs the f32 oracle
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(ref[0]),
                               atol=0.05, rtol=0.05)
    for g, r in zip(got[1:], ref[1:]):
        np.testing.assert_allclose(
            np.asarray(g, dtype=np.float32),
            np.asarray(r, dtype=np.float32), atol=1e-2, rtol=1e-2)


@pytest.mark.slow  # same budget note — tools/mega_smoke.sh covers it
def test_mega_paged_layer_vs_flash_decode_paged():
    """The per-op composition differential (the satellite's oracle
    style): same inputs through the UNFUSED pieces — jnp qk-norm/rope,
    the per-op row scatter, kernels/paged_kv.flash_decode_paged for
    the walk, jnp MLP — must agree with the fused layer."""
    layer, x, pos, w, pk, pv, table, scales = _mk_case(
        pos=[5, 13, 0], seed=2)
    got = jax.jit(lambda *a: layer(*a))(x, pos, w, pk, pv, table)
    from triton_dist_tpu.kernels.paged_kv import flash_decode_paged
    B, D = x.shape
    Hq, Hkv, hd = layer.n_heads, layer.n_kv_heads, layer.head_dim
    X = B * Hkv
    page = layer.page

    def rms(v, g, eps=1e-6):
        return v * jax.lax.rsqrt(
            jnp.mean(v * v, -1, keepdims=True) + eps) * g

    xn = rms(x, w["w_ln1"][0])
    qkv = xn @ w["w_qkv"]
    c, s = w["cos_row"], w["sin_row"]
    half = hd // 2

    def rope_head(v, g):
        v = rms(v, g)
        x1, x2 = v[:, :half], v[:, half:]
        return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)

    heads = [rope_head(qkv[:, i * hd:(i + 1) * hd],
                       w["q_norm"][0] if i < Hq else w["k_norm"][0])
             for i in range(Hq + Hkv)]
    q = jnp.stack(heads[:Hq], 1).reshape(B, 1, Hq, hd)
    k_new = jnp.stack(heads[Hq:], 1).reshape(X, hd)
    v_new = qkv[:, (Hq + Hkv) * hd:].reshape(X, hd)
    pos_x = jnp.repeat(pos, Hkv)
    pidx = table[jnp.arange(X), pos_x // page]
    r = pos_x % page
    pk2 = pk[:, 0].at[pidx, r].set(k_new.astype(pk.dtype))
    pv2 = pv[:, 0].at[pidx, r].set(v_new.astype(pv.dtype))
    lens = pos + 1
    o = flash_decode_paged(q.astype(pk.dtype), pk2, pv2, table,
                           jnp.max(lens), kv_lens=lens)
    a = o.reshape(B, Hq * hd).astype(jnp.float32)
    ores = a @ w["w_o"] + x
    on = rms(ores, w["w_ln2"][0])
    gu = on @ w["w_gu"]
    F = gu.shape[1] // 2
    y = (jax.nn.silu(gu[:, :F]) * gu[:, F:]) @ w["w_d"] + ores
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(y),
                               atol=0.05, rtol=0.05)
    np.testing.assert_allclose(np.asarray(got[1][:, 0]),
                               np.asarray(pk2), atol=1e-2, rtol=1e-2)


def test_mega_paged_trash_page_write_sink():
    """A retired slot (table rows all trash) must write ONLY the trash
    page: every other physical page comes back bitwise, live slots'
    outputs are unaffected by the retired slot's garbage row."""
    layer, x, pos, w, pk, pv, table, _ = _mk_case(pos=[5, 13, 7],
                                                  seed=3)
    # retire slot 2: its streams' rows all -> trash (page 0)
    t2 = np.array(table)
    t2[2 * layer.n_kv_heads:3 * layer.n_kv_heads, :] = 0
    t2 = jnp.asarray(t2)
    got = jax.jit(lambda *a: layer(*a))(x, pos, w, pk, pv, t2)
    ref = mega_paged_decode_layer_ref(
        x, pos, w, pk, pv, t2, n_heads=layer.n_heads,
        n_kv_heads=layer.n_kv_heads, head_dim=layer.head_dim)
    # live slots still match the oracle
    np.testing.assert_allclose(np.asarray(got[0][:2]),
                               np.asarray(ref[0][:2]),
                               atol=0.05, rtol=0.05)
    # every page the retired slot does NOT map and the live slots did
    # not write comes back BITWISE — the garbage row can only have
    # landed on the trash page
    live_pids = set(np.asarray(t2)[:2 * layer.n_kv_heads, :2]
                    .ravel().tolist())
    before_k, before_v = np.asarray(pk), np.asarray(pv)
    after_k, after_v = np.asarray(got[1]), np.asarray(got[2])
    for pid in range(1, _GEO["NP"]):
        if pid not in live_pids:
            np.testing.assert_array_equal(after_k[pid], before_k[pid])
            np.testing.assert_array_equal(after_v[pid], before_v[pid])


@pytest.mark.slow  # same budget note — tools/mega_smoke.sh covers it
def test_mega_paged_layer_int8_scale_plane_dequant():
    """INT8 pool: the fused tick's in-kernel dequant (K scales the
    logits, V folds into P) and its quantized row write must match the
    oracle built on the shared quantizer — the written int8 payload
    and scale rows are EXACT (same quantizer math), the layer output
    agrees to kernel-dot tolerance."""
    case = _mk_case(pos=[5, 13, 0], seed=4, quant=True)
    got, ref = _run_pair(*case)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(ref[0]),
                               atol=0.05, rtol=0.05)
    layer, x, pos, w, pk, pv, table, _ = case
    X = x.shape[0] * layer.n_kv_heads
    pos_x = np.repeat(np.asarray(pos), layer.n_kv_heads)
    pidx = np.asarray(table)[np.arange(X), pos_x // layer.page]
    r = pos_x % layer.page
    # written rows: int8 payload within one quantization step of the
    # oracle's (the kernel's K/V rows come out of bf16-tile matmuls,
    # the oracle's out of f32 — the SCALE/payload pair still dequants
    # to the same value within that input delta), scales close
    for gi, ri in ((1, 1), (2, 2), (3, 3), (4, 4)):
        gall = np.asarray(got[gi], np.float32)
        rall = np.asarray(ref[ri], np.float32)
        if gall.ndim == 4:   # payload planes
            gw = gall[pidx, 0, r]
            rw = rall[pidx, 0, r]
            np.testing.assert_allclose(gw, rw, atol=2.0)
        else:                # scale planes
            gw = gall[pidx, 0, r]
            rw = rall[pidx, 0, r]
            np.testing.assert_allclose(gw, rw, rtol=0.05)
    # untouched positions of the pool are bitwise identical
    mask = np.ones((_GEO["NP"], _GEO["page"]), bool)
    mask[pidx, r] = False
    np.testing.assert_array_equal(
        np.asarray(got[1])[:, 0][mask], np.asarray(pk)[:, 0][mask])
    np.testing.assert_array_equal(
        np.asarray(got[3])[:, 0][mask],
        np.asarray(case[7][0])[:, 0][mask])


# ---------------------------------------------------------------------------
# program-level: the dispatch-count delta
# ---------------------------------------------------------------------------

def _setup_serving():
    from triton_dist_tpu.models import AutoLLM
    from triton_dist_tpu.models.config import tiny_qwen3
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("tp",))
    cfg = tiny_qwen3(1, hidden_size=128, intermediate_size=256,
                     num_heads=2, num_kv_heads=1, head_dim=64,
                     dtype="bfloat16", max_position_embeddings=256)
    model = AutoLLM.from_config(cfg, mesh)
    return cfg, model


def _count_prims(jaxpr, counts):
    for eqn in jaxpr.eqns:
        counts[eqn.primitive.name] = counts.get(eqn.primitive.name,
                                                0) + 1
        if eqn.primitive.name == "pallas_call":
            # the kernel BODY is one device launch however many ops it
            # holds — that is the whole point of the fusion
            continue
        for v in eqn.params.values():
            vs = v if isinstance(v, (list, tuple)) else (v,)
            for u in vs:
                if isinstance(u, jax.extend.core.ClosedJaxpr):
                    _count_prims(u.jaxpr, counts)
                elif isinstance(u, jax.extend.core.Jaxpr):
                    _count_prims(u, counts)
    return counts


def test_mega_tick_traces_fewer_dispatches():
    """The measured win of the fused tick: the per-op paged decode
    program traces ~7+ device ops per layer (norms, projections,
    rope + scatter, the flash kernel, swiglu) where the mega program
    traces ONE pallas_call per layer — asserted on the traced
    programs, the trace-time analog of the jit-churn guard (each
    pallas_call is one device kernel launch; op count bounds the
    launch/fusion count XLA can emit)."""
    import triton_dist_tpu.models.engine as em
    cfg, model = _setup_serving()
    eng = em.Engine(model, max_seq=128, backend="mega")
    pcache = eng.make_paged_slot_cache(2, page=8)
    B = 2
    logits = jnp.zeros((B, cfg.vocab_size), jnp.float32)
    pos = jnp.zeros((B,), jnp.int32)
    active = jnp.ones((B,), bool)

    mega = jax.make_jaxpr(functools.partial(
        em._paged_slot_mega_scan_fn, gen_len=2))(
        model, logits, pcache, pos, active)
    perop = jax.make_jaxpr(functools.partial(
        em._paged_slot_scan_decode_fn, "flash", gen_len=2))(
        model, logits, pcache, pos, active)
    cm = _count_prims(mega.jaxpr, {})
    cp = _count_prims(perop.jaxpr, {})
    n_mega = sum(cm.values())
    n_perop = sum(cp.values())
    # one fused kernel per layer in the mega tick's scan body
    assert cm.get("pallas_call", 0) == cfg.num_layers, cm
    assert n_mega < n_perop, (n_mega, n_perop)
    # the per-op tick really does pay > 7 traced ops per layer
    assert n_perop > n_mega + 7 * cfg.num_layers, (n_mega, n_perop)
    print(f"traced ops/tick: mega {n_mega} vs per-op {n_perop} "
          f"({cfg.num_layers} layers; mega pallas_calls "
          f"{cm.get('pallas_call', 0)})")


# ---------------------------------------------------------------------------
# serving-level differentials
# ---------------------------------------------------------------------------

def _requests(cfg, n=3, shared=9, tail=3, gen=5, seed=1):
    from triton_dist_tpu.models.scheduler import Request
    rng = np.random.RandomState(seed)
    pre = rng.randint(0, cfg.vocab_size, size=(shared,))
    return [Request(
        rid=i,
        ids=np.concatenate(
            [pre, np.random.RandomState(7 + i).randint(
                0, cfg.vocab_size, size=(tail,))]).astype(np.int32),
        gen_len=gen) for i in range(n)]


def _near_argmax(model, reqs, streams, tol=0.05):
    """The teacher-forced logit-margin oracle (tests/test_mega.py
    convention): every emitted token's xla-oracle logit must sit
    within a bf16-scale margin of the oracle argmax — near-tie
    divergence passes, real numeric drift fails. One all-position
    forward per stream (forward_train mode='xla')."""
    fwd = jax.jit(functools.partial(model.forward_train, mode="xla"))
    for r in reqs:
        toks = np.asarray(streams[r.rid])
        assert toks.shape == (r.gen_len,), (r.rid, toks.shape)
        full = np.concatenate([np.asarray(r.ids), toks])
        logits = np.asarray(fwd(jnp.asarray(full[None], jnp.int32))[0])
        S = len(r.ids)
        for i in range(r.gen_len):
            step = logits[S + i - 1]
            gap = step.max() - step[toks[i]]
            assert gap <= tol, (r.rid, i, gap)


@pytest.mark.slow  # same budget note — the heaviest serving arm
# (43 s on the tier-1 substrate); tools/mega_smoke.sh runs it on every
# loop and the flash-vs-mega tick guard stays via the dispatch trace.
def test_mega_paged_tick_serves_per_op_streams():
    """The acceptance differential at tp=1: greedy paged+prefix-cache
    streams through backend='mega' vs backend='flash', plus mega
    overlap-on == overlap-off BITWISE (same program, deferred
    readback). Cross-backend streams are compared bitwise first and
    through the teacher-forced margin oracle on divergence (bf16
    near-ties are expected, drift is not)."""
    from triton_dist_tpu.models import Engine
    from triton_dist_tpu.models.scheduler import ContinuousScheduler
    cfg, model = _setup_serving()
    reqs = _requests(cfg)
    outs = {}
    for arm, (backend, overlap) in {
            "flash": ("flash", False), "mega": ("mega", False),
            "mega_ov": ("mega", True)}.items():
        eng = Engine(model, max_seq=128, backend=backend)
        sched = ContinuousScheduler(eng, batch=2, chunk=3, paged=True,
                                    page=8, overlap=overlap)
        outs[arm] = sched.run(_requests(cfg))
        st = sched.stats()
        if backend == "mega":
            from triton_dist_tpu.runtime.telemetry import \
                default_registry
            assert st["mega_enabled"] == 1.0
            assert st["device_wait_s_by_kind"]["mega"] > 0.0
            # process-global engine dispatch counter (the /metrics
            # surface): the fused program really ran the ticks
            assert default_registry().counter(
                "engine_mega_dispatches").value > 0
        else:
            assert st["mega_enabled"] == 0.0
    # overlap on == off is bitwise (identical program + plan)
    for r in reqs:
        np.testing.assert_array_equal(outs["mega"][r.rid],
                                      outs["mega_ov"][r.rid])
    # cross-backend: bitwise where fusion order permits, margin
    # oracle otherwise
    if not all(np.array_equal(outs["flash"][r.rid], outs["mega"][r.rid])
               for r in reqs):
        _near_argmax(model, reqs, outs["mega"])
        _near_argmax(model, reqs, outs["flash"])


def test_mega_backend_capability_errors():
    """Satellite 1: enabling mega on a live scheduler fails precisely
    or not at all — every unsupported combination names exactly what
    is missing."""
    from triton_dist_tpu.models import Engine
    from triton_dist_tpu.models.scheduler import (ContinuousScheduler,
                                                  DecodeSlots)
    cfg, model = _setup_serving()
    with pytest.raises(ValueError, match="sampled decode"):
        Engine(model, max_seq=128, backend="mega", sampling="top_k")
    with pytest.raises(ValueError, match="int8"):
        Engine(model, max_seq=128, backend="mega",
               kv_dtype=jnp.float16)
    eng = Engine(model, max_seq=128, backend="mega")
    with pytest.raises(ValueError, match="paged=True"):
        ContinuousScheduler(eng, batch=2, paged=False)
    with pytest.raises(ValueError, match="spec"):
        ContinuousScheduler(eng, batch=2, paged=True, page=8, spec=2)
    with pytest.raises(ValueError, match="PAGED decode tick only"):
        eng.slot_chunk(None, None, None, None, chunk=2)
    with pytest.raises(ValueError, match="verify"):
        eng.paged_slot_verify_chunk(None, None, None, None, None)
    # int8 kv is a PAGED capability: the contiguous decode scan says so
    eng8 = Engine(model, max_seq=128, backend="mega",
                  kv_dtype=jnp.int8)
    with pytest.raises(ValueError, match="PAGED pool"):
        eng8.decode(jnp.zeros((1, cfg.vocab_size)), None, 2)


@pytest.mark.slow
def test_mega_paged_tick_int8_pool_e2e():
    """int8-pool arm of the acceptance matrix: mega vs per-op streams
    over the scale-plane pool (in-kernel dequant end to end)."""
    from triton_dist_tpu.models import Engine
    from triton_dist_tpu.models.scheduler import ContinuousScheduler
    cfg, model = _setup_serving()
    reqs = _requests(cfg)
    outs = {}
    for backend in ("flash", "mega"):
        eng = Engine(model, max_seq=128, backend=backend,
                     kv_dtype=jnp.int8)
        sched = ContinuousScheduler(eng, batch=2, chunk=3, paged=True,
                                    page=8)
        outs[backend] = sched.run(_requests(cfg))
    if not all(np.array_equal(outs["flash"][r.rid], outs["mega"][r.rid])
               for r in reqs):
        _near_argmax(model, reqs, outs["mega"])
        _near_argmax(model, reqs, outs["flash"])


@pytest.mark.slow
def test_mega_chunked_prefill_falls_back_per_poll():
    """Mixed polls (chunked prefill in flight) run the per-op program
    under backend='mega'; pure-decode polls run the fused tick — the
    streams still match the per-op backend end to end."""
    from triton_dist_tpu.models import Engine
    from triton_dist_tpu.models.scheduler import ContinuousScheduler
    cfg, model = _setup_serving()
    reqs = _requests(cfg)
    outs = {}
    st = {}
    for backend in ("flash", "mega"):
        eng = Engine(model, max_seq=128, backend=backend)
        sched = ContinuousScheduler(eng, batch=2, chunk=3, paged=True,
                                    page=8, prefill_budget=4)
        outs[backend] = sched.run(_requests(cfg))
        st[backend] = sched.stats()
    # both tick kinds ran on the mega arm: fused decode + per-op mixed
    assert st["mega"]["device_wait_s_by_kind"]["mega"] > 0.0
    assert st["mega"]["device_wait_s_by_kind"]["mixed"] > 0.0
    if not all(np.array_equal(outs["flash"][r.rid], outs["mega"][r.rid])
               for r in reqs):
        _near_argmax(model, reqs, outs["mega"])
        _near_argmax(model, reqs, outs["flash"])


@pytest.mark.slow
def test_mega_token_server_streams():
    """Serving surface: a multi-client TokenServer burst on the mega
    engine streams token-identical to the per-op server, with the
    mega wait bucket attributed."""
    import threading
    from triton_dist_tpu.models import Engine
    from triton_dist_tpu.serving import (ByteTokenizer, TokenServer,
                                         request_stream)
    cfg, model = _setup_serving()
    tok = ByteTokenizer(cfg.vocab_size)
    prompts = [f"mega{i}!" for i in range(3)]

    def burst(backend):
        eng = Engine(model, max_seq=128, backend=backend)
        srv = TokenServer(eng, tok, batch=2, chunk=3, paged=True,
                          page=8)
        th = threading.Thread(target=srv.serve_forever,
                              kwargs=dict(max_requests=3), daemon=True)
        th.start()
        outs = {}

        def client(i):
            got = []
            for msg in request_stream(srv.host, srv.port, prompts[i],
                                      gen_len=6, timeout=300):
                got.extend(msg.get("token_ids", []))
            outs[i] = got

        ths = [threading.Thread(target=client, args=(i,))
               for i in range(3)]
        for t in ths:
            t.start()
        for t in ths:
            t.join()
        st = srv.sched.stats()
        srv.stop()
        th.join()
        return outs, st

    o_f, st_f = burst("flash")
    o_m, st_m = burst("mega")
    assert st_m["mega_enabled"] == 1.0 and st_f["mega_enabled"] == 0.0
    assert st_m["device_wait_s_by_kind"]["mega"] > 0.0, \
        st_m["device_wait_s_by_kind"]
    for i in range(3):
        assert len(o_m[i]) == 6, (i, o_m)       # streams really ran
        assert o_f[i] == o_m[i], (i, o_f[i], o_m[i])


@pytest.mark.slow
def test_mega_paged_preemption_and_resume():
    """KV-pressure preemption under the fused tick: a pool sized for
    ~1 resident forces preempt/resume churn; streams still match the
    per-op backend."""
    from triton_dist_tpu.models import Engine
    from triton_dist_tpu.models.scheduler import ContinuousScheduler
    cfg, model = _setup_serving()
    Hkv = cfg.num_kv_heads
    reqs = _requests(cfg, n=3, shared=4, tail=3, gen=6)
    worst = -(-(7 + 6 + 3 - 1) // 8)
    pool = 2 * worst * Hkv + 1 + Hkv
    outs = {}
    pre = {}
    for backend in ("flash", "mega"):
        eng = Engine(model, max_seq=128, backend=backend)
        sched = ContinuousScheduler(eng, batch=2, chunk=3, paged=True,
                                    page=8, num_pages=pool)
        outs[backend] = sched.run(_requests(cfg, n=3, shared=4,
                                            tail=3, gen=6))
        pre[backend] = sched.preemptions
    assert pre["flash"] == pre["mega"]   # identical schedule
    if not all(np.array_equal(outs["flash"][r.rid], outs["mega"][r.rid])
               for r in reqs):
        _near_argmax(model, reqs, outs["mega"])
        _near_argmax(model, reqs, outs["flash"])
