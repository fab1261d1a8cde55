"""Phi-4-mini-flash (models/phi4flash.py) against its plain reference
(benchmark/reference/phi4flash.py) on seeded random weights, at a small
size: eight layers with all five kinds (mamba, swa, mamba, swa, mamba =
half, full, gmu, cross), head size 64, a window of 16 under contexts of
up to 45, pages of 4. Logits are compared, not tokens.

The weights are the reference's own, handed to the program through the
benchmark's adapter, exactly as a chip run does it.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import phi4flash as ref
from benchmark.systems import hybrid_server
from triton_dist_tpu.models import Engine
from triton_dist_tpu.models.scheduler import ContinuousScheduler, Request

CFG = dict(
    hidden_size=256, intermediate_size=256, num_hidden_layers=8,
    num_attention_heads=4, num_key_value_heads=2, sliding_window=16,
    layer_norm_eps=1e-5, vocab_size=256, torch_dtype="float32",
    tie_word_embeddings=True,
    assumed=dict(mamba_d_state=16, mamba_d_conv=4, mamba_expand=2,
                 mamba_dt_rank=16))
SEED, PAGE, MAX_SEQ, CHUNK = 5, 4, 64, 4
TOL = 5e-5          # float32 program against float32 reference


@pytest.fixture(scope="module")
def model():
    return hybrid_server.build_model(CFG, SEED, jax.devices()[:1])


@pytest.fixture(scope="module")
def ids():
    return np.random.default_rng(0).integers(0, 256, 45).astype(np.int32)


@pytest.fixture(scope="module")
def want(ids):
    """The reference's logits at every position of `ids`."""
    return np.asarray(ref.all_logits(CFG, SEED, ids))


def _rows(slot: int, maxp: int):
    """Table row of `slot`: its own run of pages (page 0 is trash)."""
    return 1 + slot * maxp + np.arange(maxp, dtype=np.int32)


def _admit(eng, pc, slot, prompt):
    maxp = pc.table.shape[1]
    return eng.admit_slot_paged(pc, slot, prompt, _rows(slot, maxp), 0,
                                0, 0, 0)


def test_the_small_model_has_every_kind_of_layer():
    kinds = [ref.layer_kind(CFG, li) for li in range(8)]
    assert kinds == ["mamba", "swa", "mamba", "swa", "mamba", "full",
                     "gmu", "cross"]


# ----------------------------------------------------------------------
# kernels against their oracles
# ----------------------------------------------------------------------

def _scan_inputs(S, E=256, N=16, seed=0):
    k = jax.random.split(jax.random.key(seed), 6)
    x = jax.random.normal(k[0], (S, E), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(k[1], (S, E)) - 3.0)
    Bm = jax.random.normal(k[2], (S, N), jnp.float32)
    Cm = jax.random.normal(k[3], (S, N), jnp.float32)
    A = -jnp.exp(jax.random.normal(k[4], (N, E)) * 0.5)
    D = jnp.ones((E,), jnp.float32)
    s0 = jax.random.normal(k[5], (N, E), jnp.float32)
    return x, dt, Bm, Cm, A, D, s0


@pytest.mark.parametrize("valid", [None, 19, 1])
def test_selective_scan_matches_oracle(valid):
    from triton_dist_tpu.kernels import ssm
    args = _scan_inputs(24)
    n = None if valid is None else jnp.int32(valid)
    y, s = jax.jit(ssm.selective_scan)(*args, n)
    y0, s0 = ssm.selective_scan_ref(*args, n)
    upto = 24 if valid is None else valid
    np.testing.assert_allclose(y[:upto], y0[:upto], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(s, s0, rtol=1e-5, atol=1e-5)
    if valid is not None:
        # the state of the last real position, whatever the padding
        _, s_cut = ssm.selective_scan_ref(*(a[:valid] for a in args[:4]),
                                          *args[4:])
        np.testing.assert_allclose(s, s_cut, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("batch", [3, 8])
def test_ssm_step_matches_oracle_and_leaves_dead_slots_alone(batch):
    from triton_dist_tpu.kernels import ssm
    x, dt, Bm, Cm, A, D, _ = _scan_inputs(batch, seed=1)
    s = jax.random.normal(jax.random.key(9), (batch, 16, 256))
    keep = jnp.arange(batch) % 2 == 0
    y, s1 = jax.jit(ssm.ssm_step)(x, dt, Bm, Cm, A, D, s, keep)
    y0, s0 = ssm.ssm_step_ref(x, dt, Bm, Cm, A, D, s, keep)
    np.testing.assert_allclose(y, y0, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(s1, s0, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(s1[1], s[1])       # bitwise untouched


def test_ssm_step_continues_the_scan():
    """The decode update after a prefill scan equals one longer scan."""
    from triton_dist_tpu.kernels import ssm
    x, dt, Bm, Cm, A, D, s0 = _scan_inputs(17, seed=2)
    y_all, s_all = ssm.selective_scan_ref(x, dt, Bm, Cm, A, D, s0)
    _, s16 = ssm.selective_scan(*(a[:16] for a in (x, dt, Bm, Cm)),
                                A, D, s0)
    y, s17 = ssm.ssm_step(x[16:], dt[16:], Bm[16:], Cm[16:], A, D,
                          s16[None], jnp.ones((1,), bool))
    np.testing.assert_allclose(y[0], y_all[16], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(s17[0], s_all, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("window", [8, 16])
def test_flash_decode_window_matches_oracle(window):
    from triton_dist_tpu.kernels.flash_attn import (attention_cached_ref,
                                                    flash_decode)
    k = jax.random.split(jax.random.key(3), 3)
    q = jax.random.normal(k[0], (1, 16, 4, 128), jnp.float32)
    kk = jax.random.normal(k[1], (1, 1, 40, 128), jnp.float32)
    v = jax.random.normal(k[2], (1, 1, 40, 128), jnp.float32)
    got = flash_decode(q, kk, v, jnp.int32(40), scale=0.125,
                       window=window)
    want_ = attention_cached_ref(q, kk, v, jnp.int32(40), scale=0.125,
                                 window=window)
    np.testing.assert_allclose(got, want_, rtol=2e-5, atol=2e-5)
    full = attention_cached_ref(q, kk, v, jnp.int32(40), scale=0.125)
    assert float(jnp.abs(full - want_).max()) > 1e-3   # the mask bites


def test_padded_queries_compute_the_differential_pair():
    """softmax(q1 k1^T) [v1 | v2] is ordinary attention with the query
    [q1 | 0] over the pooled head [k1 | k2]: exact."""
    from triton_dist_tpu.models.phi4flash import _pad_queries
    hd, T = 64, 9
    k_ = jax.random.split(jax.random.key(4), 3)
    q = jax.random.normal(k_[0], (2, hd))                # one pair
    kp = jax.random.normal(k_[1], (T, 2 * hd))           # [k1 | k2]
    vp = jax.random.normal(k_[2], (T, 2 * hd))
    qp = _pad_queries(q)
    got = jax.nn.softmax(qp @ kp.T / 8.0, axis=-1) @ vp
    a1 = jax.nn.softmax(q[0] @ kp[:, :hd].T / 8.0) @ vp
    a2 = jax.nn.softmax(q[1] @ kp[:, hd:].T / 8.0) @ vp
    np.testing.assert_allclose(got, jnp.stack([a1, a2]), rtol=1e-5,
                               atol=1e-5)


# ----------------------------------------------------------------------
# the Engine's paged slot programs against the reference's full forward
# ----------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["xla", "flash"])
def test_prefill_then_decode_matches_reference(model, ids, want, backend):
    """Admission, then teacher-forced decode through the cache, from a
    context of 21 to 45: across the window (16) and eleven pages."""
    eng = Engine(model, max_seq=MAX_SEQ, backend=backend)
    pc = eng.make_paged_slot_cache(2, page=PAGE)
    n0 = 21
    lg, pc = _admit(eng, pc, 1, ids[:n0])
    np.testing.assert_allclose(lg, want[n0 - 1], atol=TOL)
    step = jax.jit(lambda m, t, c, p: m.forward_tokens_slots_paged(
        t, c, p, mode=backend))
    pos = jnp.array([0, n0], jnp.int32)
    for t in range(n0, len(ids)):
        lgs, pc = step(model, jnp.array([[0], [ids[t]]], jnp.int32), pc,
                       pos)
        np.testing.assert_allclose(lgs[1], want[t], atol=TOL,
                                   err_msg=f"position {t}")
        pos = pos.at[1].add(1)


@pytest.mark.parametrize("n", [1, 3, 13, 16, 17, 32, 45])
def test_admission_shortcut_equals_full_forward(model, ids, want, n):
    """Layers past half+1 run on the last prompt position only, and the
    prompt is padded to a bucket of 8: the logits are the full forward
    pass's at position n - 1, for prompts shorter than the conv's reach,
    shorter than, equal to and longer than the window."""
    eng = Engine(model, max_seq=MAX_SEQ, backend="xla")
    pc = eng.make_paged_slot_cache(1, page=PAGE)
    lg, _ = _admit(eng, pc, 0, ids[:n])
    np.testing.assert_allclose(lg, want[n - 1], atol=TOL)


def test_slot_reuse_leaks_no_state(model, ids, want):
    """Two requests of different lengths in turn through one slot: the
    second's logits are those of a fresh cache; retire zeroes the
    planes and marks the slot dead; a dead slot's planes stay alone
    while the other slot decodes."""
    eng = Engine(model, max_seq=MAX_SEQ, backend="xla")
    pc = eng.make_paged_slot_cache(2, page=PAGE)
    _, pc = _admit(eng, pc, 0, ids[:37])
    assert bool(pc.live[0]) and float(jnp.abs(pc.ssm[0][0]).max()) > 0
    pc = eng.retire_slot_paged(pc, 0)
    assert not bool(pc.live[0])
    for plane in pc.ssm + pc.conv:
        assert float(jnp.abs(plane[0]).max()) == 0.0
    assert int(pc.table[0].max()) == pc.trash
    lg, pc = _admit(eng, pc, 0, ids[:11])
    np.testing.assert_allclose(lg, want[10], atol=TOL)
    # slot 1 was never admitted: stepping the batch leaves it zero
    step = jax.jit(lambda m, t, c, p: m.forward_tokens_slots_paged(
        t, c, p, mode="xla"))
    lgs, pc = step(model, jnp.array([[ids[11]], [7]], jnp.int32), pc,
                   jnp.array([11, 0], jnp.int32))
    np.testing.assert_allclose(lgs[0], want[11], atol=TOL)
    for plane in pc.ssm + pc.conv:
        assert float(jnp.abs(plane[1]).max()) == 0.0


def test_window_bytes_do_not_grow_with_max_seq(model):
    small = Engine(model, max_seq=64, backend="xla") \
        .make_paged_slot_cache(2, page=PAGE).slot_bytes()
    large = Engine(model, max_seq=256, backend="xla") \
        .make_paged_slot_cache(2, page=PAGE).slot_bytes()
    assert small["window"] == large["window"] == 2 * 2 * 1 * 16 * 128 * 4
    assert small["state"] == large["state"] == 3 * (3 + 16) * 512 * 4
    assert small["page"] == 2 * PAGE * 128 * 4
    assert small["uniform_page"] == 4 * small["page"]


# ----------------------------------------------------------------------
# the served path
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


@pytest.mark.parametrize("backend,overlap", [("xla", False),
                                             ("flash", False),
                                             ("xla", True),
                                             ("flash", True)])
def test_scheduler_streams_are_the_references_best(model, backend,
                                                   overlap):
    """Three requests over two slots (the third reuses a slot): every
    served token is the reference's best at its position, or within
    rounding of it."""
    eng = Engine(model, max_seq=MAX_SEQ, backend=backend)
    sched = ContinuousScheduler(eng, batch=2, chunk=CHUNK, paged=True,
                                prefix_cache=False, page=PAGE,
                                overlap=overlap)
    reqs = _requests([(21, 12), (9, 20), (30, 8)])
    out = sched.run(reqs)
    assert all(len(out[r.rid]) == r.gen_len for r in reqs)
    assert float(_gaps(reqs, out).max()) < TOL
    st = sched.stats()
    assert st["cache_bytes{kind=pages}"] == 0 == st["cache_uniform_bytes"]


def test_cache_gauges_count_live_slots(model):
    eng = Engine(model, max_seq=MAX_SEQ, backend="xla")
    sched = ContinuousScheduler(eng, batch=2, chunk=CHUNK, paged=True,
                                prefix_cache=False, page=PAGE)
    sched.submit(_requests([(10, 30)])[0])
    sched.poll()
    st = sched.stats()
    sb = sched.slots.cache.slot_bytes()
    pages = -(-(10 + 30 + CHUNK - 1) // PAGE)
    assert st["cache_bytes{kind=pages}"] == pages * sb["page"]
    assert st["cache_bytes{kind=window}"] == sb["window"]
    assert st["cache_bytes{kind=state}"] == sb["state"]
    assert st["cache_uniform_bytes"] == \
        pages * sb["uniform_page"] + sb["state"]


def test_preempted_stream_is_bitwise_the_unpreempted_one(model):
    """Preemption of a slot that holds state RECOMPUTES: the victim is
    retired (state cleared, pages freed) and re-admitted later with
    prompt + emitted tokens as its prompt. Same streams as an ample
    pool, token for token."""
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
    for r in _requests(spec):
        np.testing.assert_array_equal(runs["small"][r.rid],
                                      runs["ample"][r.rid])
        assert len(runs["small"][r.rid]) == r.gen_len


@pytest.mark.parametrize("backend,batch,spec", [
    ("xla", 3, [(18, 10), (25, 6), (5, 14), (12, 9)]),
    # interpreted: the scheduler tests' batch and prompt buckets (24,
    # 32, 16), so it compiles nothing of its own; three short requests
    # over two slots, the third admitted behind a chunk in flight
    ("flash", 2, [(18, 6), (25, 5), (12, 7)])], ids=["xla", "flash"])
def test_token_server_serves_a_batch(model, backend, batch, spec):
    """Through TokenServer and its wire: streams are the reference's
    best tokens, and the server as it is built by default (dispatching
    ahead) streams what the synchronous control (overlap=False)
    streams."""
    import threading
    from triton_dist_tpu.serving import TokenServer, request_stream
    eng = Engine(model, max_seq=MAX_SEQ, backend=backend)
    reqs = _requests(spec, seed=2)

    def serve(**kw):
        srv = TokenServer(eng, hybrid_server.IdTokenizer(256),
                          batch=batch, chunk=CHUNK, paged=True,
                          prefix_cache=False, page=PAGE, **kw)
        th = threading.Thread(target=srv.serve_forever)
        th.start()
        out, errs = {}, []

        def client(r):
            toks = []
            try:
                for msg in request_stream(
                        srv.host, srv.port,
                        hybrid_server.prompt_text(r.ids),
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
        finally:
            srv.stop()
            th.join(60.0)
        assert not errs, errs
        assert srv.sched.overlap is kw.get("overlap", True)
        return out

    out = serve()
    assert all(len(out[r.rid]) == r.gen_len for r in reqs)
    assert float(_gaps(reqs, out).max()) < TOL
    assert serve(overlap=False) == out


# ----------------------------------------------------------------------
# refusals: by the capability's name, at construction
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
    assert names in str(e.value) and "recurrent state" in str(e.value)


def test_disaggregation_is_refused_by_capability(model):
    from triton_dist_tpu.models.disagg import DisaggScheduler
    with pytest.raises(ValueError, match="state handoff"):
        DisaggScheduler(Engine(model, max_seq=MAX_SEQ, backend="xla"),
                        batch=2, prefix_cache=False, page=PAGE)


def test_one_chip_only():
    from triton_dist_tpu.models.phi4flash import Phi4Flash, tiny_phi4flash
    if len(jax.devices()) < 2:
        pytest.skip("needs two devices")
    with pytest.raises(ValueError, match="tensor-parallel"):
        Phi4Flash.random_init(tiny_phi4flash(),
                              jax.make_mesh((2,), ("tp",)))


def test_qwen_models_report_their_traits():
    """The Engine asks the model, one place for both families."""
    from triton_dist_tpu.models import DenseLLM, tiny_qwen3
    m = DenseLLM.random_init(tiny_qwen3(1), jax.make_mesh((1,), ("tp",)))
    t = m.serving_traits()
    assert (t.kv_heads, t.slot_state) == (1, None)
    eng = Engine(m, max_seq=32, backend="xla")
    assert eng.traits == t
    eng.refuse_slot_state("anything", "nothing")        # a no-op here
