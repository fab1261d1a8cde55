"""Falcon-H1 (models/falcon_h1.py: a Mamba-2 mixer, layers/mamba2.py and
kernels/ssd.py, beside GQA attention, layers/tp_attn.py, in every
layer, over kv_cache.HybridSlotCache with pages AND state planes in
every layer) against its plain reference (benchmark/reference/
falcon_h1.py) on seeded random weights, at a small size: two layers of
D 64, 4 query heads on 2 KV heads of 16, 4 SSM heads of 16 channels in
2 groups, a state of 16, chunks of 8; float32. Logits are compared, not
tokens.

The weights are the reference's own, handed to the program through the
benchmark's adapter, exactly as a chip run does it.
"""

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

from benchmark.reference import falcon_h1 as ref  # noqa: E402
from benchmark.systems import falcon_h1_server  # noqa: E402
from triton_dist_tpu.kernels import ssd  # noqa: E402
from triton_dist_tpu.models import Engine  # noqa: E402
from triton_dist_tpu.models.scheduler import (ContinuousScheduler,  # noqa
                                              Request)

with open(os.path.join(_REPO, "benchmark", "testdata",
                       "tiny-falcon-h1.json")) as _f:
    CFG = json.load(_f)
SEED, PAGE, MAX_SEQ, CHUNK = 11, 4, 64, 4
# float32 program against float32 reference, on logits of std ~1: the
# two differ by the order of float32 sums alone (the chunked scan's
# matrix form against the reference's one position at a time among
# them; measured: 1.3e-6 at most over every step below). The same
# forward with its SSM state carried in bfloat16 reads ~2e-3
# (`test_tolerance_refuses_a_bfloat16_state`), forty times the
# tolerance.
TOL = 5e-5


@pytest.fixture(scope="module")
def model():
    return falcon_h1_server.build_model(CFG, SEED, jax.devices()[:1])


@pytest.fixture(scope="module")
def ids():
    return np.random.default_rng(0).integers(0, 256, 40).astype(np.int32)


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
# (a) admission, then decoding, against the reference's full forward
# ----------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["xla", "flash"])
def test_admission_then_decode_matches_the_reference(model, ids, want,
                                                     backend):
    """A 21-token prompt (not a whole number of chunks of 8) into slot
    1, then decode steps beside an empty slot 0 (19 on the oracle, 9
    through the interpreted kernels): every step's logits are the
    reference's full forward's, and the empty slot's planes stay
    zero."""
    eng = Engine(model, max_seq=MAX_SEQ, backend=backend)
    pc = eng.make_paged_slot_cache(2, page=PAGE)
    n0 = 21
    logits, pc = _admit(eng, pc, 1, ids[:n0])
    np.testing.assert_allclose(np.asarray(logits), want[n0 - 1], atol=TOL)
    step = jax.jit(lambda m, t, c, p: m.forward_tokens_slots_paged(
        t, c, p, mode=backend))
    pos = np.zeros((2,), np.int32)
    for t in range(n0, len(ids) if backend == "xla" else n0 + 9):
        tok = np.zeros((2, 1), np.int32)
        tok[1, 0], pos[1] = ids[t], t
        logits, pc = step(model, jnp.asarray(tok), pc, jnp.asarray(pos))
        np.testing.assert_allclose(np.asarray(logits)[1], want[t],
                                   atol=TOL)
    for plane in pc.conv + pc.ssm:
        assert not np.asarray(plane[0]).any()
        assert np.asarray(plane[1]).any()


def test_tolerance_refuses_a_bfloat16_state(ids, want):
    """What TOL is tight against: the reference itself with its SSM state
    rounded to bfloat16 after every update, everything else float32;
    and with the matmuls' inputs, the stream and the cache in
    bfloat16."""
    low = np.asarray(ref.all_logits(CFG, SEED, ids, precision="bf16_state"))
    assert np.abs(low - want).max() > 20 * TOL
    low = np.asarray(ref.all_logits(CFG, SEED, ids, precision="bf16"))
    assert np.abs(low - want).max() > 100 * TOL


# ----------------------------------------------------------------------
# (b), (c) the kernels against the oracles and the reference
# ----------------------------------------------------------------------

def _ssd_inputs(rng, S, H=4, P=16, G=2, N=16):
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa
    dt = jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(0.5),
                                        (S, H))), jnp.float32)
    return (f(S, H, P), dt, f(S, G, N), f(S, G, N),
            -jnp.arange(1, H + 1, dtype=jnp.float32), f(H), f(H, N, P))


@pytest.mark.parametrize("S,valid", [(21, None), (21, 13), (16, 16)],
                         ids=["21", "21-valid13", "16-valid16"])
def test_ssd_scan_matches_the_sequential_oracle(S, valid):
    x, dt, Bm, Cm, A, D, s0 = _ssd_inputs(np.random.default_rng(S), S)
    y, s = jax.jit(lambda *a: ssd.ssd_scan(*a, chunk=8))(
        x, dt, Bm, Cm, A, D, s0, valid)
    y0, s_0 = ssd.ssd_scan_ref(x, dt, Bm, Cm, A, D, s0, valid)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y0), atol=2e-5,
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_0), atol=2e-5,
                               rtol=1e-5)
    n = S if valid is None else valid
    # the state past valid_len is the state of the prefix alone
    _, s_cut = ssd.ssd_scan_ref(*(a[:n] for a in (x, dt, Bm, Cm)), A, D,
                                s0)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_cut),
                               atol=2e-5, rtol=1e-5)
    # and from zero the kernel's outputs are the reference's recurrence,
    # which keeps the published [heads, head_dim, state] layout
    z = jnp.zeros_like(s0)
    y, _ = jax.jit(lambda *a: ssd.ssd_scan(*a, chunk=8))(
        x[:n], dt[:n], Bm[:n], Cm[:n], A, D, z)
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(ref.ssm_recurrence(
            x[:n], dt[:n], Bm[:n], Cm[:n], A, D)), atol=2e-5, rtol=1e-5)


def test_ssd_step_matches_the_oracle_and_leaves_dead_slots_alone():
    rng = np.random.default_rng(3)
    x, dt, Bm, Cm, A, D, _ = _ssd_inputs(rng, 3)
    s = jnp.asarray(rng.normal(size=(3, 4, 16, 16)), jnp.float32)
    keep = jnp.asarray([True, False, True])
    y, s1 = jax.jit(ssd.ssd_step)(x, dt, Bm, Cm, A, D, s, keep)
    y0, s0 = ssd.ssd_step_ref(x, dt, Bm, Cm, A, D, s, keep)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y0), atol=1e-5)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s0), atol=1e-5)
    assert np.array_equal(np.asarray(s1[1]), np.asarray(s[1]))  # bitwise
    # a step continues the scan: the scan's state, one more token
    xs, dts, Bs, Cs, A, D, s_init = _ssd_inputs(rng, 9)
    _, s8 = ssd.ssd_scan_ref(xs[:8], dts[:8], Bs[:8], Cs[:8], A, D, s_init)
    y9, s9 = ssd.ssd_scan_ref(xs, dts, Bs, Cs, A, D, s_init)
    y, s = jax.jit(ssd.ssd_step)(xs[8:], dts[8:], Bs[8:], Cs[8:], A, D,
                                 s8[None], jnp.asarray([True]))
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(y9[8]),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(s[0]), np.asarray(s9),
                               atol=1e-5)


# ----------------------------------------------------------------------
# (d) the gated norm and the nine multipliers, against the published
# formulas (modeling_falcon_h1.py)
# ----------------------------------------------------------------------

def test_the_gated_group_norm_is_the_published_one():
    """FalconH1RMSNormGated with norm_before_gate False: gate first,
    then an RMSNorm per group of Ei / G channels, one weight of Ei."""
    from triton_dist_tpu.layers.mamba2 import gated_group_norm
    rng = np.random.default_rng(4)
    y, z = rng.normal(size=(5, 64)), rng.normal(size=(5, 64))
    w = 1 + 0.1 * rng.normal(size=64)
    h = y * (z / (1 + np.exp(-z)))
    h = h.reshape(5, 2, 32)
    h = h / np.sqrt((h ** 2).mean(-1, keepdims=True) + 1e-5)
    want = (w.reshape(2, 32) * h).reshape(5, 64)
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    got = gated_group_norm(f32(y), f32(z), f32(w), 2, 1e-5)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5,
                               atol=2e-6)
    np.testing.assert_allclose(
        np.asarray(ref.gated_norm(f32(y), f32(z), f32(w), 2, 1e-5)),
        want, rtol=2e-5, atol=2e-6)


def test_the_nine_multipliers_are_the_published_ones(model):
    """Each published multiplier where the published forward applies
    it: embedding rows and logits scaled, and the seven folded into the
    weight each follows."""
    s = ref.sizes(CFG)
    w = ref.layer_weights_fn(CFG)(ref.layer_key(SEED, 0))
    hw = ref.head_weights(CFG, SEED)
    lay = model.layers[0]
    close = lambda a, b: np.testing.assert_allclose(  # noqa: E731
        np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7)
    ids = jnp.asarray([3, 200, 7])
    close(model._embed(ids), hw["embed"][ids] * s["emb"])       # 1
    x = jax.random.normal(jax.random.key(1), (3, s["D"]), jnp.float32)
    h = ref._rms(x, hw["final_norm"], s["eps"])
    close(model._logits(x), (h @ hw["lm_head"]) * s["lm"])      # 2
    nq, nkv = s["Hq"] * s["hd"], s["Hkv"] * s["hd"]
    qkv = lay.attn.w_qkv
    close(qkv[:, :nq], w["wq"] * s["a_in"])                      # 3
    close(qkv[:, nq:nq + nkv], w["wk"] * s["a_in"] * s["key"])   # 4
    close(qkv[:, nq + nkv:], w["wv"] * s["a_in"])
    close(lay.attn.w_o, w["wo"] * s["a_out"])                    # 5
    close(lay.mamba.w_in, w["w_in"] * s["s_in"]
          * ref.mup_vector(s))                                   # 6, 7
    close(lay.mamba.w_out, w["w_out"] * s["s_out"])              # 8
    gu = lay.mlp.w_gate_up
    close(gu[:, :s["I"]], w["w_gate"] * s["mlp"][0])             # 9
    close(gu[:, s["I"]:], w["w_up"])
    close(lay.mlp.w_down, w["w_down"] * s["mlp"][1])
    assert s["mup"] == tuple(CFG["ssm_multipliers"]) and len(s["mup"]) == 5


# ----------------------------------------------------------------------
# (e) the cache: pages and state planes in every layer; retire zeroes
# ----------------------------------------------------------------------

def test_the_cache_holds_pages_and_state_in_every_layer(model):
    eng = Engine(model, max_seq=MAX_SEQ, backend="xla")
    pc = eng.make_paged_slot_cache(2, page=PAGE)
    assert len(pc.pages_k) == len(pc.pages_v) == 2 and pc.win_k == ()
    assert pc.pages_k[0].shape[1:] == (2, PAGE, 16) and pc.kv_heads == 2
    assert [a.shape for a in pc.ssm] == [(2, 4, 16, 16)] * 2
    assert [a.shape for a in pc.conv] == [(2, 3, 64 + 2 * 2 * 16)] * 2
    assert all(a.dtype == jnp.float32 for a in pc.conv + pc.ssm)
    sb = pc.slot_bytes()
    assert "window" not in sb
    assert sb["page"] == sb["uniform_page"] == 2 * 2 * 2 * PAGE * 16 * 4
    assert sb["state"] == 2 * 4 * (4 * 16 * 16 + 3 * 128)


def test_a_retired_slot_is_readmitted_from_a_zero_state(model):
    """Three requests over ONE slot on the oracle backend: each takes
    over the slot its predecessor retired from (the retire zeroes its
    planes), and every served token is the reference's best."""
    eng = Engine(model, max_seq=MAX_SEQ, backend="xla")
    pc = eng.make_paged_slot_cache(1, page=PAGE)
    _, pc = _admit(eng, pc, 0, np.arange(9, dtype=np.int32))
    assert all(np.asarray(a[0]).any() for a in pc.ssm)
    pc = pc.clear_slot(0)
    assert not any(np.asarray(a).any() for a in pc.conv + pc.ssm)
    assert not bool(pc.live[0])
    spec = [(19, 6), (11, 7), (23, 5)]
    out = ContinuousScheduler(eng, batch=1, chunk=CHUNK, paged=True,
                              prefix_cache=False, page=PAGE).run(
        _requests(spec))
    reqs = _requests(spec)
    assert all(len(out[r.rid]) == r.gen_len for r in reqs)
    assert float(_gaps(reqs, out).max()) < TOL


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


# ----------------------------------------------------------------------
# (f) refusals: by the slot state's name, at construction
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
    (lambda m: _sched(m).submit(Request(
        rid=0, ids=np.zeros(4, np.int32), gen_len=2, n=2)), "KV fork"),
    (lambda m: Engine(m, max_seq=MAX_SEQ, backend="gemm_ar"),
     "TP comm-kernel projections"),
], ids=["prefix_cache", "host_tier", "spec", "prefill_budget", "fork",
        "comm_backend"])
def test_option_is_refused_by_the_slot_states_name(model, make, names):
    with pytest.raises(ValueError, match="missing capability") as e:
        make(model)
    assert names in str(e.value) and "ssm state" in str(e.value)


def test_disaggregation_is_refused_by_capability(model):
    from triton_dist_tpu.models.disagg import DisaggScheduler
    with pytest.raises(ValueError, match="ssm state"):
        DisaggScheduler(Engine(model, max_seq=MAX_SEQ, backend="xla"),
                        batch=2, prefix_cache=False, page=PAGE)


# ----------------------------------------------------------------------
# (g) the served path
# ----------------------------------------------------------------------

def test_token_server_serves_the_references_greedy_stream(model):
    """Through TokenServer and its wire on the normal path (flash,
    dispatch-ahead): two requests over two slots; the streams are the
    reference's best tokens; the state gauges and counter are in
    stats()."""
    import threading
    from triton_dist_tpu.serving import TokenServer, request_stream
    eng = Engine(model, max_seq=MAX_SEQ, backend="flash")
    reqs = _requests([(14, 6), (11, 5)], seed=2)
    srv = TokenServer(eng, falcon_h1_server.IdTokenizer(256), batch=2,
                      chunk=CHUNK, paged=True, prefix_cache=False,
                      page=PAGE)
    th = threading.Thread(target=srv.serve_forever)
    th.start()
    out, errs = {}, []

    def client(r):
        toks = []
        try:
            for msg in request_stream(
                    srv.host, srv.port, falcon_h1_server.prompt_text(r.ids),
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
    assert all(len(out[r.rid]) == r.gen_len for r in reqs)
    assert float(_gaps(reqs, out).max()) == 0.0
    # every decode tick counts (live slots) x chunk x 2 layers
    n = st["ssm_state_updates"]
    assert n > 0 and n % (CHUNK * 2) == 0
    assert "cache_bytes{kind=state}" in st
    assert "cache_bytes{kind=pages}" in st
    assert "cache_bytes{kind=window}" not in st
