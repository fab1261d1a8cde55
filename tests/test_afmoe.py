"""Trinity (`afmoe`) as one chip's share (models/afmoe.py, layers/
gated_attn.py, kv_cache.HybridSlotCache with N paged layers beside M
rings, the stated share of layers/ep_moe.py) against its plain reference
(benchmark/reference/afmoe.py) on seeded random weights, at a small
size: eight layers, two periods of (window, window, window, full), the
first dense and seven with a shared expert beside 16 routed ones of
which this share (rank 1 of 4) holds four, top-4; 4 query heads on 2 KV
heads of 32; a WINDOW OF 8 and contexts of 3 windows and more, so every
ring wraps; float32. Logits are compared, not tokens.

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

from benchmark.reference import afmoe as ref  # noqa: E402
from benchmark.systems import afmoe_server  # noqa: E402
from triton_dist_tpu.models import Engine  # noqa: E402
from triton_dist_tpu.models.scheduler import (ContinuousScheduler,  # noqa
                                              Request)

with open(os.path.join(_REPO, "benchmark", "testdata",
                       "tiny-afmoe.json")) as _f:
    CFG = json.load(_f)
SEED, PAGE, MAX_SEQ, CHUNK, W = 11, 4, 64, 4, 8
# float32 program against float32 reference, on logits of magnitude ~3:
# the two differ by the order of float32 sums alone (measured: 4e-6 at
# most over every step below). The same forward with the matmuls'
# inputs, the stream and the cache rounded to bfloat16 where float32 is
# stated reads 0.05-0.2 (`test_tolerance_refuses_bfloat16`), a thousand
# times the tolerance.
TOL = 5e-5


def _cfg(**over):
    c = copy.deepcopy(CFG)
    dep = over.pop("deployment", None)
    c.update(over)
    if dep:
        c["deployment"].update(dep)
    return c


@pytest.fixture(scope="module")
def model():
    return afmoe_server.build_model(CFG, SEED, jax.devices()[:1])


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


def _step_fn(backend):
    return jax.jit(lambda m, t, c, p: m.forward_tokens_slots_paged(
        t, c, p, mode=backend, return_moe_stats=True))


# ----------------------------------------------------------------------
# (a) admission, then decoding, against the reference's full forward
# ----------------------------------------------------------------------

def test_the_small_model_has_both_kinds_of_both(model):
    kinds = model.config.kinds()
    assert kinds == [("swa", "dense"), ("swa", "moe"), ("swa", "moe"),
                     ("full", "moe")] + [("swa", "moe")] * 3 + [
                         ("full", "moe")]
    assert [ref.layer_kind(CFG, li) for li in range(8)] == kinds
    assert [l.attn.window for l in model.layers] == [8, 8, 8, 0] * 2
    assert [l.moe is None for l in model.layers] == [True] + [False] * 7
    moe = model.layers[1].moe
    assert moe.held == (4, 4) and moe.num_experts == 16
    assert moe.noaux == (1, 1, 2.826)
    assert moe.w_gate_up.shape[0] == 4 and moe.w_router.shape[1] == 16


@pytest.mark.parametrize("kinds", [
    [("full", "moe")], [("swa", "dense"), ("full", "moe")],
    [("swa", "moe"), ("full", "moe")]],
    ids=["full-moe", "swa-dense+full-moe", "swa-moe+full-moe"])
def test_short_stacks_of_each_kind_match_the_reference(kinds, ids):
    """One kind of attention with one kind of FFN at a time (a stack
    ends in a full layer: a model of rings alone has no pages to
    serve from)."""
    cfg = _cfg(num_hidden_layers=len(kinds),
               num_dense_layers=[f for _, f in kinds].count("dense"),
               global_attn_every_n_layers=len(kinds),
               layer_types=[{"swa": "sliding_attention",
                             "full": "full_attention"}[a]
                            for a, _ in kinds])
    assert [ref.layer_kind(cfg, li) for li in range(len(kinds))] == kinds
    m = afmoe_server.build_model(cfg, SEED, jax.devices()[:1])
    eng = Engine(m, max_seq=MAX_SEQ, backend="xla")
    pc = eng.make_paged_slot_cache(2, page=PAGE)
    logits, _ = _admit(eng, pc, 0, ids[:27])
    full = np.asarray(ref.all_logits(cfg, SEED, ids[:27]))
    np.testing.assert_allclose(np.asarray(logits), full[-1], atol=TOL)


@pytest.mark.parametrize("backend", ["xla", "flash"])
def test_admission_then_decode_through_rings_and_pages(model, ids, want,
                                                       backend):
    """Admission of a 27-token prompt (more than three windows: every
    ring wraps during it) into slot 1, then 18 decode steps beside an
    empty slot: every step's logits are the reference's full
    forward's, and the counters count what the step read."""
    eng = Engine(model, max_seq=MAX_SEQ, backend=backend)
    pc = eng.make_paged_slot_cache(2, page=PAGE)
    n0 = 27
    logits, pc = _admit(eng, pc, 1, ids[:n0])
    np.testing.assert_allclose(np.asarray(logits), want[n0 - 1], atol=TOL)
    step = _step_fn(backend)
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
        # slot 1 reads a whole ring in each of six window layers and its
        # whole context in each of two full layers; the empty slot, at
        # position 0, one row of each
        assert load[7] == 6 * (W + 1) and load[8] == 2 * (t + 1 + 1)
        assert 0 <= load[9] <= load[10] == 7 * 4
        routed, held = routed + load[5], held + load[6]
    # two slots x top-4 x seven expert layers a step, a share held
    assert routed == (len(ids) - n0) * 2 * 4 * 7 and 0 < held < routed


def test_tolerance_refuses_bfloat16(ids, want):
    """What TOL is tight against: the reference itself with the
    matmuls' inputs, the residual stream and the cached rows in
    bfloat16, where the configuration states float32."""
    low = np.asarray(ref.all_logits(CFG, SEED, ids, precision="bf16"))
    assert np.abs(low - want).max() > 100 * TOL


# ----------------------------------------------------------------------
# (b) the shares add up
# ----------------------------------------------------------------------

def test_the_shares_add_up_to_the_uncut_layer(model):
    """The routed parts of all `chips_per_layer` shares (the PROGRAM's
    `fwd_share`, each with its own rank's experts) plus the shared
    expert counted ONCE equal the uncut reference's expert layer."""
    from triton_dist_tpu.layers.ep_moe import EP_MoE
    chips = CFG["deployment"]["chips_per_layer"]
    whole = _cfg(num_experts=16,
                 deployment=dict(chips_per_layer=1, ep_rank=0))
    s = ref.sizes(whole)
    assert (s["held"], s["first"], s["E"]) == (16, 0, 16)
    li, key = 1, ref.layer_key(SEED, 1)
    f32 = lambda w: {k: v.astype(jnp.float32) for k, v in w.items()}  # noqa
    w_all = f32(ref.layer_weights_fn(whole, "moe")(key))
    m = jax.random.normal(jax.random.key(9), (40, s["D"]), jnp.float32)
    want_routed = np.asarray(ref.routed_share(m, w_all, s, "f32"))
    want_layer = want_routed + np.asarray(ref._swiglu(
        m, w_all["ws_gate"], w_all["ws_up"], w_all["ws_down"], "f32"))
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
            e_bias=w["e_bias"], noaux=(1, 1, 2.826))
        y, st = jax.jit(lambda mo, x: mo.fwd_share(x, return_stats=True))(
            moe, m)
        got += np.asarray(y)
        assert int(st["dropped"]) == 0
        # and the reference's own share is the same part
        np.testing.assert_allclose(
            np.asarray(y),
            np.asarray(ref.routed_share(m, f32(w), ref.sizes(c), "f32")),
            atol=2e-5)
    np.testing.assert_allclose(got, want_routed, atol=5e-5)
    shared = model.layers[li].mlp          # the program's shared expert
    got_layer = got + np.asarray(shared(m, "xla"))
    np.testing.assert_allclose(got_layer, want_layer, atol=5e-5)


# ----------------------------------------------------------------------
# (c) the router: bias moves selection, not weights
# ----------------------------------------------------------------------

def test_the_router_matches_the_reference():
    from triton_dist_tpu.kernels.ep_a2a import route_noaux_tc
    k = jax.random.split(jax.random.key(5), 3)
    x = jax.random.normal(k[0], (64, 32), jnp.float32)
    w_r = jax.random.normal(k[1], (32, 16), jnp.float32) * 0.3
    bias = jax.random.normal(k[2], (16,), jnp.float32) * 0.5
    route = lambda b: route_noaux_tc(  # noqa: E731
        x, w_r, b, 4, n_group=1, topk_group=1,
        routed_scaling_factor=2.826)
    w_ref, i_ref = ref.route(x, w_r, bias, k=4, route_scale=2.826)
    w, i = route(bias)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(i_ref))
    np.testing.assert_allclose(np.asarray(w), np.asarray(w_ref), atol=1e-6)
    # the bias moves the SELECTION ...
    w0, i0 = route(jnp.zeros_like(bias))
    changed = np.any(np.sort(np.asarray(i), -1)
                     != np.sort(np.asarray(i0), -1), axis=-1)
    assert changed.any() and not changed.all()
    # ... and never the WEIGHTS: they are the unbiased scores of what
    # was chosen, normalised, times the scale
    sc = np.asarray(jax.nn.sigmoid(jnp.matmul(
        x, w_r, precision=jax.lax.Precision.HIGHEST)))
    picked = np.take_along_axis(sc, np.asarray(i), axis=-1)
    np.testing.assert_allclose(
        np.asarray(w), picked / picked.sum(-1, keepdims=True) * 2.826,
        atol=1e-6)
    same = ~changed
    np.testing.assert_allclose(
        np.sort(np.asarray(w)[same], -1), np.sort(np.asarray(w0)[same], -1),
        atol=1e-6)


# ----------------------------------------------------------------------
# (d) positions: none on a full layer, differences on a window layer
# ----------------------------------------------------------------------

def test_a_full_layer_has_no_positions_and_a_window_layer_differences(
        model):
    from triton_dist_tpu.layers.gated_attn import prefill_attention
    cfg = model.config
    P_, shift = 20, 13
    u = jax.random.normal(jax.random.key(3), (P_, cfg.hidden_size),
                          jnp.float32)
    at = lambda p0: model._rope_rows(model.rope[p0:p0 + P_])  # noqa

    def through(attn, p0):
        q, k, v, g = attn.project(u, at(p0))
        o = prefill_attention(q, k, v, window=attn.window,
                              scale=attn.scale, impl="ref", scope="x")
        return np.asarray(q), np.asarray(k), np.asarray(attn.out(o, g))

    full, swa = model.layers[3].attn, model.layers[1].attn
    # a full layer reads no table: the same rows, bit for bit, wherever
    # the positions start
    for a, b in zip(through(full, 0), through(full, shift)):
        np.testing.assert_array_equal(a, b)
    # a window layer's rotated q and k move with the position ...
    q0, k0, y0 = through(swa, 0)
    q1, k1, y1 = through(swa, shift)
    assert np.abs(k1 - k0).max() > 0.1
    # ... its scores, and so its output, depend on t - s alone
    s0 = np.einsum("qhd,khd->hqk", q0, np.repeat(k0, 2, axis=1))
    s1 = np.einsum("qhd,khd->hqk", q1, np.repeat(k1, 2, axis=1))
    np.testing.assert_allclose(s1, s0, atol=2e-4)
    np.testing.assert_allclose(y1, y0, atol=2e-5)
    # and the reference says the same of its own scores
    w = ref.layer_weights_fn(CFG, "moe")(ref.layer_key(SEED, 1))
    want = np.asarray(ref.scores(CFG, "swa", u, w)) * cfg.head_dim ** 0.5
    np.testing.assert_allclose(s0, want, atol=2e-4)


# ----------------------------------------------------------------------
# (e) the ring after it wrapped
# ----------------------------------------------------------------------

def test_the_ring_holds_the_last_window_rotated_at_its_positions(model,
                                                                  ids):
    """After a 27-token admission and 6 steps (t = 32) row r of a ring
    holds the key of the one position in t-7 .. t congruent to r,
    rotated at THAT position, in every window layer; the pages of a
    full layer hold every position's unrotated key."""
    eng = Engine(model, max_seq=MAX_SEQ, backend="xla")
    pc = eng.make_paged_slot_cache(2, page=PAGE)
    n0, t_end = 27, 32
    _, pc = _admit(eng, pc, 0, ids[:n0])
    step = _step_fn("xla")
    for t in range(n0, t_end + 1):
        tok = np.zeros((2, 1), np.int32)
        tok[0, 0] = ids[t]
        _, pc, _ = step(model, jnp.asarray(tok), pc,
                        jnp.asarray([t, 0], jnp.int32))
    # the reference's keys of every position, layer by layer
    s = ref.sizes(CFG)
    rope = ref.rope_tables(CFG, t_end + 1)
    hw = ref.head_weights(CFG, SEED)
    x = ref.embed(CFG, hw["embed"], ids[:t_end + 1])
    i_win = i_full = 0
    for li in range(s["L"]):
        a_kind, f_kind = ref.layer_kind(CFG, li)
        w = ref.layer_weights_fn(CFG, f_kind)(ref.layer_key(SEED, li))
        w32 = {k: v.astype(jnp.float32) for k, v in w.items()}
        _, k, v, _ = ref._qkvg(ref._rms(x, w32["ln_in"], s["eps"]), w32,
                               rope, s, a_kind, "f32")
        k, v = np.asarray(k), np.asarray(v)
        if a_kind == "swa":
            held = np.arange(t_end - W + 1, t_end + 1)
            assert sorted(held % W) == list(range(W))
            ring_k = np.asarray(pc.win_k[i_win])[0]      # [Hkv, W, d]
            ring_v = np.asarray(pc.win_v[i_win])[0]
            for p in held:
                np.testing.assert_allclose(ring_k[:, p % W], k[p],
                                           atol=2e-5)
                np.testing.assert_allclose(ring_v[:, p % W], v[p],
                                           atol=2e-5)
            i_win += 1
        else:
            pool = np.asarray(pc.pages_k[i_full])        # [NP, 2Hkv, pg, d]
            rows = _rows(0, pc.table.shape[1])
            for p in range(t_end + 1):
                got = pool[rows[p // PAGE], :, p % PAGE]
                np.testing.assert_allclose(got[:s["Hkv"]], k[p], atol=2e-5)
                np.testing.assert_allclose(got[s["Hkv"]:], v[p], atol=2e-5)
            i_full += 1
        x = ref.layer_forward(CFG, li, x, w, rope)
    assert (i_win, i_full) == (6, 2)


def test_the_cache_is_rings_and_pages_and_no_state(model):
    eng = Engine(model, max_seq=MAX_SEQ, backend="xla")
    pc = eng.make_paged_slot_cache(2, page=PAGE)
    assert len(pc.pages_k) == 2 and pc.pages_v == ()
    assert len(pc.win_k) == len(pc.win_v) == 6
    assert pc.conv == () and pc.ssm == ()
    assert pc.pages_k[0].shape[1:] == (2 * 2, PAGE, 32)
    assert pc.win_k[0].shape == (2, 2, W, 32) and pc.kv_heads == 2
    sb = pc.slot_bytes()
    assert "state" not in sb
    assert sb["page"] == 2 * 2 * 2 * PAGE * 32 * 4       # 2 layers, K and V
    assert sb["window"] == 6 * 2 * 2 * W * 32 * 4
    assert sb["uniform_page"] == 8 * 2 * 2 * PAGE * 32 * 4


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
    g = ref.served_token_gaps(CFG, SEED, seqs, [len(r.ids) for r in reqs],
                              pad_to=16, block_rows=16)
    return np.concatenate(g["f32"])


def test_scheduler_reuses_slots_and_rings(model):
    """Three requests over two slots on the oracle backend: the third
    takes over a retired slot whose rings still hold its predecessor's
    rows, and every served token is the reference's best."""
    eng = Engine(model, max_seq=MAX_SEQ, backend="xla")
    spec = [(19, 12), (26, 10), (9, 14)]
    sched = ContinuousScheduler(eng, batch=2, chunk=CHUNK, paged=True,
                                prefix_cache=False, page=PAGE)
    out = sched.run(_requests(spec))
    reqs = _requests(spec)
    assert all(len(out[r.rid]) == r.gen_len for r in reqs)
    assert float(_gaps(reqs, out).max()) < TOL


def test_token_server_serves_the_references_greedy_stream(model):
    """Through TokenServer and its wire on the normal path (flash,
    dispatch-ahead): three requests over two slots; the streams are the
    reference's best tokens; the counters and gauges of what this model
    adds are in stats()."""
    import threading
    from triton_dist_tpu.serving import TokenServer, request_stream
    eng = Engine(model, max_seq=MAX_SEQ, backend="flash")
    reqs = _requests([(18, 6), (25, 5), (12, 7)], seed=2)
    srv = TokenServer(eng, afmoe_server.IdTokenizer(256), batch=2,
                      chunk=CHUNK, paged=True, prefix_cache=False,
                      page=PAGE)
    th = threading.Thread(target=srv.serve_forever)
    th.start()
    out, errs = {}, []

    def client(r):
        toks = []
        try:
            for msg in request_stream(
                    srv.host, srv.port, afmoe_server.prompt_text(r.ids),
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
    finally:
        srv.stop()
        th.join(60.0)
    assert not errs, errs
    assert srv.sched.overlap is True
    assert all(len(out[r.rid]) == r.gen_len for r in reqs)
    # the served stream IS the reference's greedy stream: at every
    # position the served token is the reference's own best
    assert float(_gaps(reqs, out).max()) == 0.0
    routed, held = st["moe_pairs_routed"], st["moe_pairs_held"]
    assert routed > 0 and 0 < held < routed
    assert st.get("moe_capacity_drops", 0) == 0
    assert sum(st.get(f"expert_tokens{{expert={e}}}", 0)
               for e in range(4, 8)) == held
    assert "expert_tokens{expert=0}" not in st
    win, full = (st["attn_kv_positions{kind=window}"],
                 st["attn_kv_positions{kind=full}"])
    assert 0 < win and 0 < full
    assert 0 < st["moe_experts_touched"] <= st["moe_experts_offered"]
    assert "cache_bytes{kind=pages}" in st
    assert "cache_bytes{kind=window}" in st
    assert "cache_bytes{kind=state}" not in st
    assert "cache_uniform_bytes" in st


def test_cache_gauges_count_live_pages_and_rings(model):
    eng = Engine(model, max_seq=MAX_SEQ, backend="xla")
    sched = ContinuousScheduler(eng, batch=2, chunk=CHUNK, paged=True,
                                prefix_cache=False, page=PAGE)
    sched.submit(_requests([(10, 30)])[0])
    sched.poll()
    st = sched.stats()
    pages = -(-(10 + 30 + CHUNK - 1) // PAGE)
    row = 2 * 2 * 32 * 4                 # K and V, 2 heads of 32, float32
    assert st["cache_bytes{kind=pages}"] == pages * 2 * PAGE * row
    assert st["cache_bytes{kind=window}"] == 6 * W * row
    assert st["cache_uniform_bytes"] == pages * 8 * PAGE * row
    assert "cache_bytes{kind=state}" not in st


# ----------------------------------------------------------------------
# (g) refusals: by the slot state's name, at construction
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
def test_option_is_refused_by_the_slot_states_name(model, make, names):
    with pytest.raises(ValueError, match="missing capability") as e:
        make(model)
    assert names in str(e.value) and "window rings" in str(e.value)


def test_disaggregation_is_refused_by_capability(model):
    from triton_dist_tpu.models.disagg import DisaggScheduler
    with pytest.raises(ValueError, match="window rings"):
        DisaggScheduler(Engine(model, max_seq=MAX_SEQ, backend="xla"),
                        batch=2, prefix_cache=False, page=PAGE)


def test_one_chip_only_and_a_share_inside_the_experts():
    from triton_dist_tpu.models.afmoe import Afmoe, tiny_afmoe
    with pytest.raises(ValueError, match="share of 4 of the router's 16"):
        Afmoe.random_init(tiny_afmoe(held_first=14),
                          jax.make_mesh((1,), ("tp",)))
    if len(jax.devices()) < 2:
        pytest.skip("needs two devices")
    with pytest.raises(ValueError, match="tensor-parallel gated"):
        Afmoe.random_init(tiny_afmoe(), jax.make_mesh((2,), ("tp",)))


def test_random_init_serves_and_reports_its_traits():
    from triton_dist_tpu.models.afmoe import Afmoe, tiny_afmoe
    m = Afmoe.random_init(tiny_afmoe(), jax.make_mesh((1,), ("tp",)))
    t = m.serving_traits()
    assert (t.kv_heads, t.slot_state, t.own_pool) == (2, "window rings",
                                                      None)
    eng = Engine(m, max_seq=MAX_SEQ, backend="xla")
    assert eng.traits == t
    out = ContinuousScheduler(eng, batch=2, chunk=CHUNK, paged=True,
                              prefix_cache=False, page=PAGE).run(
        _requests([(11, 5)]))
    assert len(out[0]) == 5
