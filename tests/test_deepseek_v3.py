"""DeepSeek-V3 as one chip's share (models/deepseek.py, layers/
mla_attn.py, the stated share of layers/ep_moe.py) against its plain
reference (benchmark/reference/deepseek_v3.py) on seeded random weights,
at a small size: three layers (dense, expert, expert), 4 heads, 16
routed experts in 4 groups of which this share (rank 1 of 4) holds four,
top-4, float32. Logits are compared, not tokens.

The weights are the reference's own, handed to the program through the
benchmark's adapter, exactly as a chip run does it.
"""

import copy
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)        # `benchmark` is a top-level package

from benchmark.reference import deepseek_v3 as ref  # noqa: E402
from benchmark.systems import deepseek_server  # noqa: E402
from triton_dist_tpu.models import Engine  # noqa: E402
from triton_dist_tpu.models.scheduler import (ContinuousScheduler,  # noqa
                                              Request)

with open(os.path.join(_REPO, "benchmark", "testdata",
                       "tiny-deepseek-v3.json")) as _f:
    CFG = json.load(_f)
SEED, PAGE, MAX_SEQ, CHUNK = 11, 4, 64, 4
TOL = 5e-5          # float32 program against float32 reference


def _cfg(**over):
    c = copy.deepcopy(CFG)
    dep = over.pop("deployment", None)
    c.update(over)
    if dep:
        c["deployment"].update(dep)
    return c


@pytest.fixture(scope="module")
def model():
    return deepseek_server.build_model(CFG, SEED, jax.devices()[:1])


@pytest.fixture(scope="module")
def ids():
    return np.random.default_rng(0).integers(0, 256, 45).astype(np.int32)


@pytest.fixture(scope="module")
def want(ids):
    """The reference's logits at every position of `ids`."""
    return np.asarray(ref.all_logits(CFG, SEED, ids))


def _rows(slot: int, maxp: int):
    return 1 + slot * maxp + np.arange(maxp, dtype=np.int32)


def _admit(eng, pc, slot, prompt):
    return eng.admit_slot_paged(pc, slot, prompt,
                                _rows(slot, pc.table.shape[1]), 0, 0, 0, 0)


# ----------------------------------------------------------------------
# (a) every kind of layer, and the whole model, against the reference
# ----------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["dense", "moe"])
def test_one_layer_of_each_kind_matches_the_reference(kind, ids):
    cfg = _cfg(num_hidden_layers=1,
               first_k_dense_replace=1 if kind == "dense" else 0)
    assert ref.layer_kind(cfg, 0) == kind
    m = deepseek_server.build_model(cfg, SEED, jax.devices()[:1])
    assert [l.kind for l in m.layers] == [kind]
    eng = Engine(m, max_seq=MAX_SEQ, backend="xla")
    pc = eng.make_paged_slot_cache(2, page=PAGE)
    logits, _ = _admit(eng, pc, 0, ids[:24])
    full = np.asarray(ref.all_logits(cfg, SEED, ids[:24]))
    np.testing.assert_allclose(np.asarray(logits), full[-1], atol=TOL)


def test_the_small_model_has_both_kinds_and_a_share(model):
    assert [l.kind for l in model.layers] == ["dense", "moe", "moe"]
    moe = model.layers[1].moe
    assert moe.held == (4, 4) and moe.num_experts == 16
    assert moe.w_gate_up.shape[0] == 4 and moe.w_router.shape[1] == 16


@pytest.mark.parametrize("backend", ["xla", "flash"])
def test_prefill_then_decode_through_the_latent_pool(model, ids, want,
                                                     backend):
    """Admission (expanded attention) of a 21-token prompt into slot 1,
    then 24 decode steps (absorbed, through the pool) beside an empty
    slot: every step's logits are the reference's full forward's."""
    eng = Engine(model, max_seq=MAX_SEQ, backend=backend)
    pc = eng.make_paged_slot_cache(2, page=PAGE)
    n0 = 21
    logits, pc = _admit(eng, pc, 1, ids[:n0])
    np.testing.assert_allclose(np.asarray(logits), want[n0 - 1], atol=TOL)
    step = jax.jit(lambda m, t, c, p: m.forward_tokens_slots_paged(
        t, c, p, mode=backend, return_moe_stats=True))
    pos = np.zeros((2,), np.int32)
    routed = held = 0
    for t in range(n0, len(ids)):
        tok = np.zeros((2, 1), np.int32)
        tok[1, 0], pos[1] = ids[t], t
        logits, pc, load = step(model, jnp.asarray(tok), pc,
                                jnp.asarray(pos))
        np.testing.assert_allclose(np.asarray(logits)[1], want[t],
                                   atol=TOL)
        load = np.asarray(load)
        assert load[4] == 0                       # nothing dropped
        assert load[:4].sum() == load[6]
        routed, held = routed + load[5], held + load[6]
    # two slots x top-4 x two expert layers a step, a share of them held
    assert routed == (len(ids) - n0) * 2 * 4 * 2 and 0 < held < routed


# ----------------------------------------------------------------------
# (b) the two attends are one attention
# ----------------------------------------------------------------------

@pytest.mark.parametrize("impl", ["ref", "flash"])
def test_absorbed_decode_equals_expanded_attention(model, impl):
    """The same latent rows, attended absorbed (one query a step over
    the pool) and expanded (the whole prompt at once): the outputs of
    every position agree."""
    from triton_dist_tpu.models.kv_cache import LatentSlotCache
    attn = model.layers[0].attn
    P_ = 24
    u = jax.random.normal(jax.random.key(3), (P_, CFG["hidden_size"]),
                          jnp.float32)
    pc = LatentSlotCache.create_latent(
        1, 1, MAX_SEQ, rank=attn.rank, rope=attn.rope,
        expanded_row_values=0, page=PAGE, num_pages=32, mesh=model.mesh,
        dtype=jnp.float32)
    rows = jnp.asarray(_rows(0, MAX_SEQ // PAGE))
    p = jnp.arange(P_)
    expanded, pool = attn.prefill(u, model.cos[:P_], model.sin[:P_],
                                  pc.pages_k[0], rows[p // PAGE], p % PAGE,
                                  impl=impl)
    table = rows[None]
    fresh = pc.pages_k[0]
    for t in range(P_):
        out, fresh = attn.decode(u[t:t + 1], model.cos[t:t + 1],
                                 model.sin[t:t + 1], fresh, table,
                                 jnp.asarray([t]), impl=impl)
        np.testing.assert_allclose(np.asarray(out[0]),
                                   np.asarray(expanded[t]), atol=2e-5)
    # and decode wrote the rows the prefill wrote
    np.testing.assert_allclose(np.asarray(fresh), np.asarray(pool),
                               atol=1e-6)


# ----------------------------------------------------------------------
# (c) grouped sigmoid routing
# ----------------------------------------------------------------------

def test_route_noaux_tc_matches_the_reference():
    from triton_dist_tpu.kernels.ep_a2a import route_noaux_tc
    k = jax.random.split(jax.random.key(5), 3)
    x = jax.random.normal(k[0], (64, 32), jnp.float32)
    w_r = jax.random.normal(k[1], (32, 16), jnp.float32) * 0.3
    bias = jax.random.normal(k[2], (16,), jnp.float32) * 0.5
    kw = dict(k=4, groups=4, topk_group=2, route_scale=2.5)
    w_ref, i_ref = ref.route(x, w_r, bias, **kw)
    w, i = route_noaux_tc(x, w_r, bias, 4, n_group=4, topk_group=2,
                          routed_scaling_factor=2.5)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(i_ref))
    np.testing.assert_allclose(np.asarray(w), np.asarray(w_ref), atol=1e-6)
    # the bias moves the SELECTION ...
    w0, i0 = route_noaux_tc(x, w_r, jnp.zeros_like(bias), 4, n_group=4,
                            topk_group=2, routed_scaling_factor=2.5)
    changed = np.any(np.sort(np.asarray(i), -1)
                     != np.sort(np.asarray(i0), -1), axis=-1)
    assert changed.any() and not changed.all()
    # ... and never the WEIGHTS: they are the unbiased scores of what
    # was chosen, normalised, times the scale
    sc = np.asarray(jax.nn.sigmoid(jnp.matmul(
        x, w_r, precision=jax.lax.Precision.HIGHEST)))
    picked = np.take_along_axis(sc, np.asarray(i), axis=-1)
    np.testing.assert_allclose(
        np.asarray(w), picked / picked.sum(-1, keepdims=True) * 2.5,
        atol=1e-6)
    # where the selection is the same, so are the weights
    same = ~changed
    np.testing.assert_allclose(
        np.sort(np.asarray(w)[same], -1), np.sort(np.asarray(w0)[same], -1),
        atol=1e-6)
    # at most topk_group groups are ever chosen from
    assert (np.array([len(set(r // 4)) for r in np.asarray(i)]) <= 2).all()


# ----------------------------------------------------------------------
# (d) the shares add up
# ----------------------------------------------------------------------

def test_the_shares_add_up_to_the_uncut_layer(model):
    """The routed parts of all `chips_per_layer` shares (the PROGRAM's
    `fwd_share`, each with its own rank's experts) plus the shared
    expert counted once equal the uncut reference's expert layer."""
    from triton_dist_tpu.layers.ep_moe import EP_MoE
    chips = CFG["deployment"]["chips_per_layer"]
    whole = _cfg(n_routed_experts=16,
                 deployment=dict(chips_per_layer=1, ep_rank=0))
    s = ref.sizes(whole)
    assert (s["held"], s["first"], s["E"]) == (16, 0, 16)
    li, key = 1, ref.layer_key(SEED, 1)
    f32 = lambda w: {k: v.astype(jnp.float32) for k, v in w.items()}  # noqa
    w_all = f32(ref.layer_weights_fn(whole, "moe")(key))
    u = jax.random.normal(jax.random.key(9), (1, 40, s["D"]), jnp.float32)
    want_routed = np.asarray(ref.routed_share(u, w_all, s, "f32"))[0]
    want_layer = want_routed + np.asarray(ref._swiglu(
        u, w_all["ws_gate"], w_all["ws_up"], w_all["ws_down"], "f32"))[0]
    got = np.zeros_like(want_routed)
    for r in range(chips):
        c = _cfg(deployment=dict(ep_rank=r))
        w = ref.layer_weights_fn(c, "moe")(key)
        first = r * 4
        # a share's experts ARE the uncut layer's
        np.testing.assert_array_equal(
            np.asarray(w["we_down"]),
            np.asarray(w_all["we_down"][first:first + 4]))
        moe = EP_MoE.init(
            w["w_router"], w["we_gate"], w["we_up"], w["we_down"],
            mesh=model.mesh, axis="tp", top_k=4,
            capacity_factor="dropless", held=(first, 4),
            e_bias=w["e_bias"], noaux=(4, 2, 2.5))
        y, st = jax.jit(lambda m, x: m.fwd_share(x, return_stats=True))(
            moe, u[0])
        got += np.asarray(y)
        assert int(st["dropped"]) == 0
        # and the reference's own share is the same part
        np.testing.assert_allclose(
            np.asarray(y),
            np.asarray(ref.routed_share(u, f32(w), ref.sizes(c),
                                        "f32"))[0], atol=2e-5)
    np.testing.assert_allclose(got, want_routed, atol=5e-5)
    shared = model.layers[li].mlp          # the program's shared expert
    got_layer = got + np.asarray(shared(u[0], "xla"))
    np.testing.assert_allclose(got_layer, want_layer, atol=5e-5)


# ----------------------------------------------------------------------
# (e) a skewed routing drops nothing
# ----------------------------------------------------------------------

def test_a_routing_skewed_onto_one_held_expert_drops_nothing(model):
    from triton_dist_tpu.layers.ep_moe import EP_MoE
    rng = np.random.default_rng(2)
    D, F, T = 128, 128, 96
    router = np.zeros((D, 16), np.float32)
    router[:, 5] = 1.0                  # every token's best: expert 5
    wg, wu = (rng.normal(size=(4, D, F)).astype(np.float32) * D ** -0.5
              for _ in range(2))
    wd = rng.normal(size=(4, F, D)).astype(np.float32) * F ** -0.5
    moe = EP_MoE.init(router, wg, wu, wd, mesh=model.mesh, axis="tp",
                      top_k=4, capacity_factor="dropless", held=(4, 4),
                      e_bias=np.zeros((16,), np.float32), noaux=(4, 2, 2.5))
    x = jnp.asarray(np.abs(rng.normal(size=(T, D))) + 0.1, jnp.float32)
    y, st = jax.jit(lambda m, x: m.fwd_share(x, return_stats=True))(moe, x)
    counts = np.asarray(st["expert_tokens"])
    assert int(st["dropped"]) == 0
    assert counts[1] == T               # all 96 pairs on held expert 5
    assert int(st["pairs_routed"]) == T * 4
    assert int(st["pairs_held"]) == counts.sum()
    wts, idx = ref.route(x, jnp.asarray(router), jnp.zeros((16,)), k=4,
                         groups=4, topk_group=2, route_scale=2.5)
    want = np.zeros((T, D), np.float32)
    for e in range(4):
        g = np.sum(np.where(np.asarray(idx) == 4 + e, np.asarray(wts), 0),
                   -1)
        h = np.asarray(x) @ wg[e]
        want += g[:, None] * ((h / (1 + np.exp(-h)) * (np.asarray(x)
                                                       @ wu[e])) @ wd[e])
    np.testing.assert_allclose(np.asarray(y), want, atol=5e-5)


# ----------------------------------------------------------------------
# (f) the served path
# ----------------------------------------------------------------------

def _requests(spec, seed=1):
    rng = np.random.default_rng(seed)
    return [Request(rid=i, ids=rng.integers(0, 256, n).astype(np.int32),
                    gen_len=g) for i, (n, g) in enumerate(spec)]


def _gaps(reqs, out):
    seqs = [list(map(int, r.ids)) + list(map(int, out[r.rid]))
            for r in reqs]
    g = ref.served_token_gaps(CFG, SEED, seqs, [len(r.ids) for r in reqs])
    return np.concatenate(g["f32"])


def test_preempted_stream_is_bitwise_the_unpreempted_one(model):
    """A pool too small for both streams: the victim is retired (pages
    freed) and re-admitted later with prompt + emitted tokens as its
    prompt, the third request reuses a slot. Same streams as an ample
    pool, token for token, and every token the reference's best."""
    eng = Engine(model, max_seq=MAX_SEQ, backend="xla")
    spec = [(10, 12), (14, 10), (7, 9)]
    worst = -(-(14 + 12 + CHUNK - 1) // PAGE)
    runs = {}
    for label, npages in (("small", worst + 1 + 1), ("ample", None)):
        sched = ContinuousScheduler(
            eng, batch=2, chunk=CHUNK, paged=True, prefix_cache=False,
            page=PAGE, num_pages=npages)
        runs[label] = sched.run(_requests(spec))
        if label == "small":
            assert sched.preemptions > 0 and not sched.rejected
    reqs = _requests(spec)
    for r in reqs:
        np.testing.assert_array_equal(runs["small"][r.rid],
                                      runs["ample"][r.rid])
        assert len(runs["small"][r.rid]) == r.gen_len
    assert float(_gaps(reqs, runs["small"]).max()) < TOL


def test_token_server_serves_a_batch_and_exports_the_counters(model):
    """Through TokenServer and its wire on the normal path (flash,
    dispatch-ahead): three requests over two slots; the streams are the
    reference's best tokens; the share's counters and gauges are in
    stats() and on /metrics' registry."""
    import threading
    from triton_dist_tpu.serving import TokenServer, request_stream
    eng = Engine(model, max_seq=MAX_SEQ, backend="flash")
    reqs = _requests([(18, 6), (25, 5), (12, 7)], seed=2)
    srv = TokenServer(eng, deepseek_server.IdTokenizer(256), batch=2,
                      chunk=CHUNK, paged=True, prefix_cache=False,
                      page=PAGE)
    th = threading.Thread(target=srv.serve_forever)
    th.start()
    out, errs = {}, []

    def client(r):
        toks = []
        try:
            for msg in request_stream(
                    srv.host, srv.port, deepseek_server.prompt_text(r.ids),
                    gen_len=r.gen_len, timeout=300.0):
                if msg.get("done"):
                    if msg.get("error"):
                        errs.append(msg["error"])
                    break
                toks.extend(msg.get("token_ids") or [])
        except Exception as e:                   # surfaced below
            errs.append(repr(e))
        out[r.rid] = toks

    try:
        clients = [threading.Thread(target=client, args=(r,))
                   for r in reqs]
        for c in clients:
            c.start()
        for c in clients:
            c.join(600.0)
        st = srv.stats()
        text = srv.sched.tele.registry.prometheus_text() \
            if hasattr(srv.sched.tele.registry, "prometheus_text") else ""
    finally:
        srv.stop()
        th.join(60.0)
    assert not errs, errs
    assert srv.sched.overlap is True
    assert all(len(out[r.rid]) == r.gen_len for r in reqs)
    assert float(_gaps(reqs, out).max()) < TOL
    routed, held = st["moe_pairs_routed"], st["moe_pairs_held"]
    assert routed > 0 and 0 < held < routed
    assert st.get("moe_capacity_drops", 0) == 0
    assert sum(st.get(f"expert_tokens{{expert={e}}}", 0)
               for e in range(4, 8)) == held
    assert "expert_tokens{expert=0}" not in st
    assert st["expert_load_imbalance"] >= 1.0
    assert st["kv_page_copy_bytes"] == PAGE * 256 * 4   # a padded row x 4 B
    assert "cache_bytes{kind=latent}" in st
    assert "cache_uniform_bytes" in st
    if text:
        assert "moe_pairs_held" in text


def test_cache_gauges_count_live_latent_pages(model):
    eng = Engine(model, max_seq=MAX_SEQ, backend="xla")
    sched = ContinuousScheduler(eng, batch=2, chunk=CHUNK, paged=True,
                                prefix_cache=False, page=PAGE)
    sched.submit(_requests([(10, 30)])[0])
    sched.poll()
    st = sched.stats()
    pages = -(-(10 + 30 + CHUNK - 1) // PAGE)
    # 3 layers x 4 positions x (128 + 16) values x 4 B a page, as
    # published; expanded K and V would be 4 heads x (48 + 32) values
    assert st["cache_bytes{kind=latent}"] == pages * 3 * PAGE * 144 * 4
    assert st["cache_uniform_bytes"] == pages * 3 * PAGE * 320 * 4


# ----------------------------------------------------------------------
# (g) refusals: by the capability's name, at construction
# ----------------------------------------------------------------------

def _sched(model, **kw):
    opts = dict(batch=2, chunk=CHUNK, paged=True, prefix_cache=False,
                page=PAGE)
    opts.update(kw)
    return ContinuousScheduler(
        Engine(model, max_seq=MAX_SEQ, backend="xla"), **opts)


@pytest.mark.parametrize("make,names", [
    (lambda m: _sched(m, prefix_cache=True), "prefix reuse"),
    (lambda m: _sched(m, host_pool_pages=8), "host KV tier"),
    (lambda m: _sched(m, spec=2), "speculative verify"),
    (lambda m: _sched(m, prefill_budget=8), "chunked prefill"),
    (lambda m: _sched(m, paged=False), "contiguous cache"),
    (lambda m: _sched(m).submit(Request(
        rid=0, ids=np.zeros(4, np.int32), gen_len=2, n=2)), "KV fork"),
    (lambda m: Engine(m, max_seq=MAX_SEQ, backend="gemm_ar"),
     "TP comm-kernel projections"),
    (lambda m: Engine(m, max_seq=MAX_SEQ, backend="xla",
                      kv_dtype=jnp.int8), "int8 pool"),
    (lambda m: Engine(m, max_seq=MAX_SEQ, backend="xla").prefill(
        np.zeros((1, 8), np.int32)), "contiguous cache"),
], ids=["prefix_cache", "host_tier", "spec", "prefill_budget",
        "contiguous_slots", "fork", "comm_backend", "int8_kv",
        "engine_prefill"])
def test_option_is_refused_by_capability(model, make, names):
    with pytest.raises(ValueError, match="missing capability") as e:
        make(model)
    assert names in str(e.value) and "a latent pool" in str(e.value)


def test_disaggregation_is_refused_by_capability(model):
    from triton_dist_tpu.models.disagg import DisaggScheduler
    with pytest.raises(ValueError, match="a latent pool"):
        DisaggScheduler(Engine(model, max_seq=MAX_SEQ, backend="xla"),
                        batch=2, prefix_cache=False, page=PAGE)


def test_one_chip_only_and_a_share_inside_the_experts():
    from triton_dist_tpu.models.deepseek import DeepSeekV3, tiny_deepseek
    with pytest.raises(ValueError, match="share of 4 of the router's 16"):
        DeepSeekV3.random_init(tiny_deepseek(held_first=14),
                               jax.make_mesh((1,), ("tp",)))
    if len(jax.devices()) < 2:
        pytest.skip("needs two devices")
    with pytest.raises(ValueError, match="tensor-parallel latent"):
        DeepSeekV3.random_init(tiny_deepseek(),
                               jax.make_mesh((2,), ("tp",)))


def test_the_model_reports_its_traits(model):
    t = model.serving_traits()
    assert (t.kv_heads, t.slot_state, t.own_pool) == (1, None,
                                                      "a latent pool")
    assert Engine(model, max_seq=MAX_SEQ, backend="flash").traits == t
