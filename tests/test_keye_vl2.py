"""Keye-VL-2.0's language model as one chip's share (models/qwen_moe.py
with `sa_config`, layers/sparse_attn.py, kv_cache.IndexedSlotCache, the
stated share of layers/ep_moe.py) against its plain reference
(benchmark/reference/keye_vl2.py) on seeded random weights, at a small
size: two layers, 8 query heads over 2 KV heads of 32, an indexer of 4
heads of 16 that keeps 16 positions, 16 routed experts of which this
share (rank 1 of 4) holds four, top-4, float32. Logits are compared,
not tokens.

The weights are the reference's own, handed to the program through the
benchmark's adapter, exactly as a chip run does it.

Tolerances: the program and the reference compute in float32 here, in
another order of summation (kernels' blocks, the paged walk's online
softmax): 5e-5 on logits of magnitude ~1 is a hundred float32 ulps of
room and a thousand times under what a wrong position, page, mask or
set would move them by (0.01-1). Selected SETS are compared exactly: a
near-tie within an ulp at the sixteenth score would be needed to split
them, and none occurs on these seeds.
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

from benchmark.reference import keye_vl2 as ref  # noqa: E402
from benchmark.systems import keye_server  # noqa: E402
from triton_dist_tpu.models import Engine  # noqa: E402
from triton_dist_tpu.models.scheduler import (ContinuousScheduler,  # noqa
                                              Request)

with open(os.path.join(_REPO, "benchmark", "testdata",
                       "tiny-keye-vl2.json")) as _f:
    CFG = json.load(_f)
SEED, PAGE, MAX_SEQ, CHUNK = 11, 4, 128, 4
TOPK = CFG["sa_config"]["topk"]
TOL = 5e-5          # float32 program against float32 reference
POOL = "K/V and index-key planes"


def _cfg(**over):
    c = copy.deepcopy(CFG)
    dep = over.pop("deployment", None)
    c.update(over)
    if dep:
        c["deployment"].update(dep)
    return c


@pytest.fixture(scope="module")
def model():
    return keye_server.build_model(CFG, SEED, jax.devices()[:1])


@pytest.fixture(scope="module")
def ids():
    return np.random.default_rng(0).integers(0, 256, 60).astype(np.int32)


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
# (a) the whole model against the reference, below and above topk
# ----------------------------------------------------------------------

def test_the_small_model_is_a_share_with_an_indexer(model):
    from triton_dist_tpu.layers.sparse_attn import SA_Attn
    assert len(model.layers) == 2
    moe, attn = model.layers[1].moe, model.layers[1].attn
    assert moe.held == (4, 4) and moe.num_experts == 16 and moe.noaux is None
    assert moe.w_gate_up.shape[0] == 4 and moe.w_router.shape[1] == 16
    assert isinstance(attn, SA_Attn) and attn.topk == TOPK
    assert (attn.n_heads, attn.n_kv_heads, attn.idx_heads, attn.idx_dim,
            attn.sections) == (8, 2, 4, 16, (4, 6, 6))
    assert model.config.expert_ids == range(4, 8)


@pytest.mark.parametrize("backend,n0", [("xla", 10), ("xla", 37),
                                        ("flash", 10), ("flash", 37)])
def test_prefill_then_decode_through_the_pool_and_the_plane(model, ids,
                                                            want, backend,
                                                            n0):
    """Admission of an n0-token prompt into slot 1, then decode to 60
    positions beside an empty slot: every step's logits are the
    reference's full forward's. n0 = 10 admits with everything selected
    (10 < topk = 16) and decodes ACROSS topk, where selection starts to
    bind; n0 = 37 admits with selection binding from row 16 on. Then the
    slot is retired and REUSED for another prompt."""
    eng = Engine(model, max_seq=MAX_SEQ, backend=backend)
    pc = eng.make_paged_slot_cache(2, page=PAGE)
    logits, pc = _admit(eng, pc, 1, ids[:n0])
    np.testing.assert_allclose(np.asarray(logits), want[n0 - 1], atol=TOL)
    step = jax.jit(lambda m, t, c, p: m.forward_tokens_slots_paged(
        t, c, p, mode=backend, return_moe_stats=True))
    pos = np.zeros((2,), np.int32)
    ctx = att = routed = held = 0
    for t in range(n0, len(ids)):
        tok = np.zeros((2, 1), np.int32)
        tok[1, 0], pos[1] = ids[t], t
        logits, pc, load = step(model, jnp.asarray(tok), pc,
                                jnp.asarray(pos))
        np.testing.assert_allclose(np.asarray(logits)[1], want[t],
                                   atol=TOL)
        load = np.asarray(load)
        assert load[4] == 0 and load[:4].sum() == load[6]
        # two layers x (the empty slot's one position + this slot's)
        assert load[7] == 2 * (1 + t + 1)
        assert load[8] == 2 * (1 + min(t + 1, TOPK))
        routed, held = routed + load[5], held + load[6]
        ctx, att = ctx + load[7], att + load[8]
    assert routed == (len(ids) - n0) * 2 * 4 * 2 and 0 < held < routed
    assert att < ctx
    # slot reuse: retire, admit another prompt into the same slot
    pc = eng.retire_slot_paged(pc, 1)
    other = ids[::-1][:23].copy()
    logits, pc = _admit(eng, pc, 1, other)
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(ref.all_logits(CFG, SEED, other))[-1],
        atol=TOL)


# ----------------------------------------------------------------------
# (b) the two attends are one attention, and their sets the reference's
# ----------------------------------------------------------------------

def _layer_inputs(model, P_, key=3, batch=1, max_seq=MAX_SEQ):
    from triton_dist_tpu.models.kv_cache import IndexedSlotCache
    u = jax.random.normal(jax.random.key(key), (P_, CFG["hidden_size"]),
                          jnp.float32)
    maxp = max_seq // PAGE
    pc = IndexedSlotCache.create_indexed(
        1, batch, max_seq, n_kv_heads=2, head_dim=32, index_dim=16,
        page=PAGE, num_pages=batch * maxp + 8, mesh=model.mesh,
        dtype=jnp.float32)
    return u, pc, jnp.asarray(_rows(0, maxp))


@pytest.mark.parametrize("impl", ["ref", "flash"])
def test_decode_form_equals_prefill_form_and_the_references_sets(model,
                                                                 impl):
    """The same rows, attended a token at a time through the pool and a
    whole prompt at once: the outputs of every position agree, the two
    forms choose the same sets, and those are the reference's
    (`jax.lax.top_k` on its own float32 scores)."""
    attn = model.layers[0].attn
    P_ = 40
    u, pc, rows = _layer_inputs(model, P_)
    p = jnp.arange(P_)
    rope, rope_i = attn.rope_of(model.cos, model.sin, model.cos_i,
                                model.sin_i, p)
    whole, kv, ix, sets = attn.prefill(
        u, rope, rope_i, pc.pages_k[0], pc.pages_i[0], rows[:P_ // PAGE],
        0, impl=impl, return_sets=True)
    w = ref.layer_weights_fn(CFG)(ref.layer_key(SEED, 0))
    want_out, want_sets = ref.attention(CFG, u, w)
    np.testing.assert_array_equal(np.asarray(sets), np.asarray(want_sets))
    assert np.asarray(want_sets)[-1].sum() == TOPK    # selection binds
    np.testing.assert_allclose(np.asarray(whole), np.asarray(want_out),
                               atol=2e-5)
    table = rows[None]
    kv1, ix1 = pc.pages_k[0], pc.pages_i[0]

    @jax.jit
    def step(a, m, u1, kv1, ix1, t):
        r, ri = a.rope_of(m.cos, m.sin, m.cos_i, m.sin_i, t)
        return a.decode(u1, r, ri, kv1, ix1, table, t, impl=impl,
                        return_sets=True)

    for t in range(P_):
        out, kv1, ix1, n_att, sel = step(attn, model, u[t:t + 1], kv1,
                                         ix1, jnp.asarray([t]))
        np.testing.assert_allclose(np.asarray(out[0]),
                                   np.asarray(whole[t]), atol=2e-5)
        np.testing.assert_array_equal(np.asarray(sel[0, :P_]),
                                      np.asarray(want_sets[t]))
        # the count is the mask's own, and the mask keeps topk
        assert int(n_att[0]) == int(sel[0].sum()) == min(t + 1, TOPK)
    # and decode wrote the rows the prefill wrote
    np.testing.assert_allclose(np.asarray(kv1), np.asarray(kv), atol=1e-6)
    np.testing.assert_allclose(np.asarray(ix1), np.asarray(ix), atol=1e-6)


def test_the_broken_selection_control_departs_only_where_selection_binds(
        ids, want):
    """`--control sel_last` (the reference attending the last `topk`
    positions whatever the indexer says) is what the cell's limit has
    to refuse: it is the float32 reference itself while everything is
    selected, and another model from the first position past `topk`."""
    got = np.asarray(ref.all_logits(CFG, SEED, ids, precision="sel_last"))
    np.testing.assert_allclose(got[:TOPK], want[:TOPK], atol=1e-6)
    assert np.abs(got[TOPK + 8:] - want[TOPK + 8:]).max() > 1e-2
    gaps = ref.served_token_gaps(
        CFG, SEED, [list(map(int, ids))], [TOPK + 8],
        precisions=("f32", "sel_last"), block_rows=32, pad_to=64)
    assert float(np.mean(gaps["sel_last"][0])) > 1e-3


def test_select_topk_is_top_k_with_ties_to_the_lower_position():
    from triton_dist_tpu.kernels.sparse_attn import select_topk
    rng = np.random.default_rng(4)
    sc = rng.normal(size=(12, 96)).astype(np.float32)
    sc[:, ::5] = 0.25                    # ties, some at the k-th score
    sc[3] = 0.0                          # a whole row of ties
    sc[4, 10:30] = -np.inf
    lens = np.array([96, 50, 7, 96, 96, 1, 0, 33, 96, 96, 20, 96])
    valid = np.arange(96)[None] < lens[:, None]
    got = np.asarray(jax.jit(lambda s, v: select_topk(s, v, 16))(
        jnp.asarray(sc), jnp.asarray(valid)))
    for r in range(12):
        n = int(lens[r])
        want = np.zeros((96,), bool)
        if n:
            _, idx = jax.lax.top_k(jnp.asarray(sc[r, :n]), min(16, n))
            want[np.asarray(idx)] = True
        np.testing.assert_array_equal(got[r], want, err_msg=f"row {r}")


# ----------------------------------------------------------------------
# (b') the index plane and the one kernel that scores it
# ----------------------------------------------------------------------

_BLOCK = 256        # the kernel's block in these tests (2,048 as served)


def _index_case(values, key, S, M, Hi, d, P_):
    """Queries, weights and keys of P_ positions a slot. "integers":
    small whole numbers, a weight a power of two: every product and sum
    is exact in float32 whatever their order (and would not be in
    bfloat16: a dot reaches 16 x 49), so the kernel equals the oracle to
    0.0 by what it computes and not by how XLA's CPU backend happens to
    order or fuse a float sum, which is all that "normals" can differ
    by (a few ulps of a score: 2e-6, relative and absolute)."""
    ks = jax.random.split(jax.random.key(key), 3)
    if values == "integers":
        draw = lambda k, sh: jax.random.randint(  # noqa: E731
            k, sh, -7, 8).astype(jnp.float32)
        w = 2.0 ** jax.random.randint(ks[2], (S, M, Hi), -2, 3)
        w = w * jnp.where(jnp.arange(Hi) % 3 == 1, -1.0, 1.0)
    else:
        draw = lambda k, sh: jax.random.normal(k, sh, jnp.float32)  # noqa
        w = jax.random.normal(ks[2], (S, M, Hi), jnp.float32)
    return (draw(ks[0], (S, M, Hi, d)), w, draw(ks[1], (S, P_, d)),
            0.0 if values == "integers" else 2e-6)


@pytest.mark.parametrize("d", [16, 64])
@pytest.mark.parametrize("values", ["integers", "normals"])
def test_index_scores_of_ragged_slots_in_one_call(monkeypatch, values, d):
    """The decode step's shape: one query row a slot over the plane, the
    slots' lengths ragged in ONE call: an empty slot, one key, one
    short of a block's edge, the edge, one past it, a length that ends
    in a block's first half, the full plane."""
    from triton_dist_tpu.kernels import sparse_attn as sa
    monkeypatch.setattr(sa, "INDEX_BLOCK", _BLOCK)
    lens = [0, 1, _BLOCK - 1, _BLOCK, _BLOCK + 1, 2 * _BLOCK + 100,
            4 * _BLOCK]
    S, Hi, L = len(lens), 4, 4 * _BLOCK
    qi, w, keys, tol = _index_case(values, 5, S, 1, Hi, d, L)
    rows, lanes = sa.index_plane_shape(L, d)
    assert (rows, lanes, sa.index_block(rows)) == (L // 2, 128, _BLOCK)
    plane = jax.vmap(lambda k: sa.pack_index_keys(k, rows, lanes))(keys)
    assert plane.shape == (S, rows, lanes)
    got = np.asarray(jax.jit(lambda *a: sa.index_scores(*a, scale=0.125))(
        qi, w, plane, jnp.asarray(lens, jnp.int32)))
    assert got.shape == (S, 1, L)
    for s_, n in enumerate(lens):
        want = np.asarray(sa.index_scores_ref(qi[s_], w[s_], keys[s_, :n],
                                              scale=0.125))
        np.testing.assert_allclose(got[s_, :, :n], want, rtol=tol, atol=tol,
                                   err_msg=f"slot {s_}, {n} keys")


@pytest.mark.parametrize("kv_len", [_BLOCK, 2 * _BLOCK + 88, 3 * _BLOCK])
@pytest.mark.parametrize("values", ["integers", "normals"])
def test_index_scores_of_an_admission_block(monkeypatch, values, kv_len):
    """The admission's shape: ONE "slot" of 256 query rows over a
    prompt's packed keys, of which `kv_len` count: whole blocks, and a
    last block that ends in its first half."""
    from triton_dist_tpu.kernels import sparse_attn as sa
    monkeypatch.setattr(sa, "INDEX_BLOCK", _BLOCK)
    M, Hi, d, P_ = 256, 4, 16, 3 * _BLOCK - 40
    qi, w, keys, tol = _index_case(values, 6, 1, M, Hi, d, P_)
    kp = sa.pack_index_keys(keys[0], 4 * _BLOCK, 128)
    assert kp.shape == (3 * _BLOCK // 2, 128)
    got = np.asarray(jax.jit(lambda *a: sa.index_scores(*a, scale=0.125))(
        qi, w, kp[None], jnp.asarray([kv_len], jnp.int32)))
    n = min(kv_len, P_)
    want = np.asarray(sa.index_scores_ref(qi[0], w[0], keys[0, :n],
                                          scale=0.125))
    np.testing.assert_allclose(got[0, :, :n], want, rtol=tol, atol=tol)


@pytest.mark.parametrize("impl", ["ref", "flash"])
def test_index_plane_round_trip(model, monkeypatch, impl):
    """An admission's write into slot 1's run, then appends for three
    slots at once: slot 0 from its first row, slot 1 across the packed
    row's two halves (positions 120..131: 128 is where a block's second
    half starts), slot 2 across a block's edge (250..261). Read back
    position for position, every key is the one written, and what
    nobody wrote is zero."""
    from triton_dist_tpu.kernels import sparse_attn as sa
    monkeypatch.setattr(sa, "INDEX_BLOCK", _BLOCK)
    attn = model.layers[0].attn
    B, P_, steps, max_seq = 3, 120, 12, 600
    u, pc, rows = _layer_inputs(model, P_, key=12, batch=B, max_seq=max_seq)
    assert pc.pages_i[0].shape == (B, 3 * _BLOCK // 2, 128)
    maxp = max_seq // PAGE
    table = jnp.asarray(np.stack([_rows(b, maxp) for b in range(B)]))
    tables = lambda p: attn.rope_of(model.cos, model.sin,  # noqa: E731
                                    model.cos_i, model.sin_i, p)
    rope, rope_i = tables(jnp.arange(P_))
    _, kv, ix = attn.prefill(u, rope, rope_i, pc.pages_k[0], pc.pages_i[0],
                             table[1, :P_ // PAGE], 1, impl=impl)
    want = np.zeros((B, 3 * _BLOCK, 16), np.float32)
    want[1, :P_] = np.asarray(attn.project(u, rope, rope_i)[3])

    @jax.jit
    def step(a, u1, kv, ix, t):
        r, ri = tables(t)
        out = a.decode(u1, r, ri, kv, ix, table, t, impl=impl)
        return out[1], out[2], a.project(u1, r, ri)[3]

    start = np.array([0, P_, 250], np.int32)
    for t in range(steps):
        u1 = jax.random.normal(jax.random.key(100 + t),
                               (B, CFG["hidden_size"]), jnp.float32)
        kv, ix, ki = step(attn, u1, kv, ix, jnp.asarray(start + t))
        want[np.arange(B), start + t] = np.asarray(ki)
    got = np.asarray(sa.unpack_index_keys(ix, 16))
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-6)
    # the lanes a 16-wide key does not fill stay zero
    assert not np.asarray(ix).reshape(B, -1, 2, 64)[..., 16:].any()


# ----------------------------------------------------------------------
# (c) the three-section rotary on unequal components
# ----------------------------------------------------------------------

def _positions3(P_):
    """An image's worth of positions: time stands still over a 6 x 5
    grid after 8 text tokens, then text goes on."""
    t = np.arange(P_)
    pos = np.stack([t, t, t])
    g = np.arange(30)
    pos[:, 8:38] = np.stack([np.full(30, 8), 8 + g // 5, 8 + g % 5])
    pos[:, 38:] = pos[:, 38:] - 30 + 6
    return pos.astype(np.int32)


def test_rotary_takes_three_position_components(model):
    from triton_dist_tpu.layers.common import apply_rope
    pos = _positions3(48)
    assert (pos[0] != pos[1]).any() and (pos[1] != pos[2]).any()
    x = jax.random.normal(jax.random.key(6), (48, 3, 32), jnp.float32)
    cos, sin, _, _ = ref.rope_tables(CFG, pos)
    got = apply_rope(x, model.cos, model.sin, jnp.asarray(pos), (4, 6, 6))
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref._rope(x, cos, sin)),
                               atol=2e-6)
    # a text token's three equal components read what one position reads
    t = jnp.arange(48)
    np.testing.assert_array_equal(
        np.asarray(apply_rope(x, model.cos, model.sin, t)),
        np.asarray(apply_rope(x, model.cos, model.sin,
                              jnp.stack([t, t, t]), (4, 6, 6))))


@pytest.mark.parametrize("impl", ["ref", "flash"])
def test_attention_on_multimodal_positions_matches_the_reference(model,
                                                                 impl):
    attn = model.layers[1].attn
    P_ = 48
    pos = _positions3(P_)
    u, pc, rows = _layer_inputs(model, P_, key=8)
    p = jnp.arange(P_)
    rope, rope_i = attn.rope_of(model.cos, model.sin, model.cos_i,
                                model.sin_i, jnp.asarray(pos))
    got, _, _, sets = attn.prefill(
        u, rope, rope_i, pc.pages_k[0], pc.pages_i[0], rows[:P_ // PAGE],
        0, impl=impl, return_sets=True)
    w = ref.layer_weights_fn(CFG)(ref.layer_key(SEED, 1))
    want_out, want_sets = ref.attention(CFG, u, w, positions=pos)
    np.testing.assert_array_equal(np.asarray(sets), np.asarray(want_sets))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want_out),
                               atol=2e-5)
    text_out, _ = ref.attention(CFG, u, w)
    assert float(jnp.abs(text_out - want_out).max()) > 1e-3


# ----------------------------------------------------------------------
# (d) the shares add up
# ----------------------------------------------------------------------

def test_the_shares_add_up_to_the_uncut_layer(model):
    """The routed parts of all `chips_per_layer` shares (the PROGRAM's
    `fwd_share`, each with its own rank's experts; softmax routing over
    all 16) equal the uncut reference's expert layer."""
    from triton_dist_tpu.layers.ep_moe import EP_MoE
    chips = CFG["deployment"]["chips_per_layer"]
    whole = _cfg(num_experts=16, num_local_experts=16,
                 deployment=dict(chips_per_layer=1, ep_rank=0))
    s = ref.sizes(whole)
    assert (s["held"], s["first"], s["E"]) == (16, 0, 16)
    key = ref.layer_key(SEED, 1)
    f32 = lambda w: {k: v.astype(jnp.float32) for k, v in w.items()}  # noqa
    w_all = f32(ref.layer_weights_fn(whole)(key))
    u = jax.random.normal(jax.random.key(9), (40, s["D"]), jnp.float32)
    want = np.asarray(ref.routed_share(u, w_all, s, "f32"))
    got = np.zeros_like(want)
    for r in range(chips):
        c = _cfg(deployment=dict(ep_rank=r))
        w = ref.layer_weights_fn(c)(key)
        first = r * 4
        # a share's experts ARE the uncut layer's
        np.testing.assert_array_equal(
            np.asarray(w["we_down"]),
            np.asarray(w_all["we_down"][first:first + 4]))
        moe = EP_MoE.init(
            w["w_router"], w["we_gate"], w["we_up"], w["we_down"],
            mesh=model.mesh, axis="tp", top_k=4,
            capacity_factor="dropless", held=(first, 4))
        y, st = jax.jit(lambda m, x: m.fwd_share(x, return_stats=True))(
            moe, u)
        got += np.asarray(y)
        assert int(st["dropped"]) == 0
        assert int(st["pairs_routed"]) == 40 * 4
        # and the reference's own share is the same part
        np.testing.assert_allclose(
            np.asarray(y),
            np.asarray(ref.routed_share(u, f32(w), ref.sizes(c), "f32")),
            atol=2e-5)
    np.testing.assert_allclose(got, want, atol=5e-5)


# ----------------------------------------------------------------------
# (e) the served path
# ----------------------------------------------------------------------

def _requests(spec, seed=1):
    rng = np.random.default_rng(seed)
    return [Request(rid=i, ids=rng.integers(0, 256, n).astype(np.int32),
                    gen_len=g) for i, (n, g) in enumerate(spec)]


def _gaps(reqs, out):
    seqs = [list(map(int, r.ids)) + list(map(int, out[r.rid]))
            for r in reqs]
    g = ref.served_token_gaps(CFG, SEED, seqs, [len(r.ids) for r in reqs],
                              pad_to=16)
    return np.concatenate(g["f32"])


def test_preempted_stream_is_bitwise_the_unpreempted_one(model):
    """A pool too small for both streams: the victim is retired (pages
    freed) and re-admitted later with prompt + emitted tokens as its
    prompt, the third request reuses a slot. Same streams as an ample
    pool, token for token, and every token the reference's best."""
    eng = Engine(model, max_seq=MAX_SEQ, backend="xla")
    spec = [(20, 14), (26, 12), (9, 11)]
    worst = -(-(26 + 14 + CHUNK - 1) // PAGE)
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
    dispatch-ahead): three requests over two slots, contexts past topk;
    the streams are the reference's best tokens; the share's and the
    indexer's counters and gauges are in stats() and on /metrics'
    registry."""
    import threading
    from triton_dist_tpu.serving import TokenServer, request_stream
    eng = Engine(model, max_seq=MAX_SEQ, backend="flash")
    reqs = _requests([(18, 6), (25, 5), (12, 7)], seed=2)
    srv = TokenServer(eng, keye_server.IdTokenizer(256), batch=2,
                      chunk=CHUNK, paged=True, prefix_cache=False,
                      page=PAGE)
    th = threading.Thread(target=srv.serve_forever)
    th.start()
    out, errs = {}, []

    def client(r):
        toks = []
        try:
            for msg in request_stream(
                    srv.host, srv.port, keye_server.prompt_text(r.ids),
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
    ctx, att = st["sa_positions_in_context"], st["sa_positions_attended"]
    assert 0 < att < ctx
    assert 0 < st["moe_experts_touched"] <= st["moe_experts_offered"]
    # a page's K and V for both heads: 2 x 2 x 4 x 32 values x 4 B
    assert st["kv_page_copy_bytes"] == 2 * 2 * PAGE * 32 * 4
    assert "cache_bytes{kind=pages}" in st and "cache_bytes{kind=index}" in st
    assert "cache_uniform_bytes" in st
    if text:
        assert "sa_positions_attended" in text


def test_request_line_cap_follows_the_engines_max_seq(model):
    """A 16,384-id prompt is ~120 KB of text: the wire's cap on a
    request line is the larger of 64 KiB and 16 bytes a position of
    `max_seq`, so a long-context server takes the prompts that fill its
    slots and still refuses a firehose by its size."""
    import threading
    from triton_dist_tpu.runtime.chaos import oversized_client
    from triton_dist_tpu.serving import TokenServer, _MAX_LINE
    tok = keye_server.IdTokenizer(256)
    opts = dict(batch=1, chunk=CHUNK, paged=True, prefix_cache=False,
                page=PAGE)
    short = TokenServer(Engine(model, max_seq=MAX_SEQ, backend="xla"), tok,
                        **opts)
    assert short._max_line == _MAX_LINE == 65536
    short.stop()
    srv = TokenServer(Engine(model, max_seq=16384, backend="xla"), tok,
                      **opts)
    assert srv._max_line == 16 * 16384
    th = threading.Thread(target=srv.serve_forever)
    th.start()
    try:
        # 128 KiB: over the short server's cap, under this one's: read
        # whole and refused for what it IS (not JSON), not for its size
        ok = oversized_client("127.0.0.1", srv.port, nbytes=1 << 17)
        assert ok is not None and "bad request" in ok["error"], ok
        big = oversized_client("127.0.0.1", srv.port, nbytes=1 << 19)
        assert big is not None and "exceeds 262144" in big["error"], big
    finally:
        srv.stop()
        th.join(60.0)


def test_cache_gauges_count_live_pages_of_both_kinds(model):
    eng = Engine(model, max_seq=MAX_SEQ, backend="xla")
    sched = ContinuousScheduler(eng, batch=2, chunk=CHUNK, paged=True,
                                prefix_cache=False, page=PAGE)
    sched.submit(_requests([(10, 30)])[0])
    sched.poll()
    st = sched.stats()
    pages = -(-(10 + 30 + CHUNK - 1) // PAGE)
    # 2 layers x 4 positions x (2 x 2 heads x 32) values x 4 B of K and
    # V a page; 16 index values a position as published (the plane pads
    # them to 128 lanes)
    assert st["cache_bytes{kind=pages}"] == pages * 2 * PAGE * 128 * 4
    assert st["cache_bytes{kind=index}"] == pages * 2 * PAGE * 16 * 4
    assert st["cache_uniform_bytes"] == st["cache_bytes{kind=pages}"]


# ----------------------------------------------------------------------
# (f) refusals: by the capability's name, at construction
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
    assert names in str(e.value) and POOL in str(e.value)


def test_disaggregation_is_refused_by_capability(model):
    from triton_dist_tpu.models.disagg import DisaggScheduler
    with pytest.raises(ValueError, match=POOL):
        DisaggScheduler(Engine(model, max_seq=MAX_SEQ, backend="xla"),
                        batch=2, prefix_cache=False, page=PAGE)


def test_one_chip_only_and_a_share_inside_the_experts():
    from triton_dist_tpu.models.config import SAConfig, tiny_qwen3_moe
    from triton_dist_tpu.models.qwen_moe import Qwen3MoE
    cfg = tiny_qwen3_moe(1, num_heads=4, num_kv_heads=2, num_experts=16,
                         num_experts_per_tok=4,
                         sa_config=SAConfig(4, 16, 16),
                         mrope_section=(4, 6, 6), held_experts=(14, 4))
    with pytest.raises(ValueError, match="share of 4 of the router's 16"):
        Qwen3MoE.random_init(cfg, jax.make_mesh((1,), ("tp",)))
    if len(jax.devices()) < 2:
        pytest.skip("needs two devices")
    import dataclasses
    with pytest.raises(ValueError, match="tensor-parallel sparse"):
        Qwen3MoE.random_init(
            dataclasses.replace(cfg, held_experts=(4, 4)),
            jax.make_mesh((2,), ("tp",)))


def test_the_model_reports_its_traits_and_the_plain_stack_its_own(model):
    from triton_dist_tpu.models import AutoLLM
    from triton_dist_tpu.models.config import tiny_qwen3_moe
    t = model.serving_traits()
    assert (t.kv_heads, t.slot_state, t.own_pool) == (2, None, POOL)
    assert Engine(model, max_seq=MAX_SEQ, backend="flash").traits == t
    # sa_config=None stays the Qwen3-MoE stack: K and V pages the
    # Engine's own programs move
    plain = AutoLLM.from_config(tiny_qwen3_moe(1), model.mesh)
    assert plain.serving_traits().own_pool is None
    assert plain.cos_i is None
    assert plain.config.expert_ids == range(0, 2)
