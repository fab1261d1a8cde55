"""Chunked prefill (Sarathi-Serve): stall-free mixed prefill+decode
batching — the exactness matrix and the stall bound.

Contract (models/scheduler.py module docstring): with `prefill_budget`
set, an admission's prompt prefills in token-budgeted chunks FUSED into
the regular decode step (one mixed forward per poll), so live streams
keep emitting while a long prompt is absorbed — and every stream is
BITWISE identical to the monolithic-admission scheduler across
{greedy, sampled, spec=K} x {contiguous, paged+prefix-cache}. The
chunked state must also compose with every serving feature shipped
before it: preemption mid-prefill (exact resume through the radix
tree), cancel and deadline expiry mid-prefill (pages freed, the
zero-leak invariant `available + outstanding == num_pages` holds), and
the prefix-cache boundary-page copy-on-write (once, at chunk 0).

The perf claim under test (the acceptance criterion): the most prefill
work a live stream ever waits on between two of its tokens — measured
as prompt tokens pushed through a single poll's forward,
stats()["max_prefill_tokens_per_poll"] — is bounded by prefill_budget,
where the monolithic scheduler pays the full prompt suffix in one
poll (the head-of-line stall Sarathi-Serve measures as inter-token
latency spikes).
"""

import jax
import numpy as np
import pytest

from triton_dist_tpu.models import (AutoLLM, ContinuousScheduler, Engine,
                                    Request)
from triton_dist_tpu.models.config import tiny_qwen3

mesh = None


def setup_module(module):
    global mesh
    n = len(jax.devices())
    mesh = jax.make_mesh((n,), ("tp",))


def _model():
    n = mesh.shape["tp"]
    cfg = tiny_qwen3(n)
    return cfg, AutoLLM.from_config(cfg, mesh)


def _mixed_requests(cfg, shared_prefix=None, seed=0):
    """Short and LONG prompts interleaved (5 requests, batch < 5 forces
    a mid-stream admission into a recycled slot); odd rids share a
    prefix when one is given (the paged+prefix-cache case)."""
    rng = np.random.RandomState(seed)
    spec = [(5, 6), (20, 8), (3, 4), (12, 10), (7, 9)]
    out = []
    for i, (L, g) in enumerate(spec):
        ids = rng.randint(0, cfg.vocab_size, size=(L,)).astype(np.int32)
        if shared_prefix is not None and i % 2:
            ids = np.concatenate([shared_prefix, ids]).astype(np.int32)
        out.append(Request(rid=i, ids=ids, gen_len=g, seed=100 + i))
    return out


def _assert_same_streams(mono, chunked):
    assert set(mono) == set(chunked)
    for rid in mono:
        np.testing.assert_array_equal(
            chunked[rid], mono[rid],
            err_msg=f"rid={rid}: chunked stream diverged from "
                    f"monolithic")


# ----------------------------------------------------------------------
# the exactness matrix: {greedy, sampled, spec=K} x {contiguous,
# paged+prefix-cache}, chunked vs monolithic, bitwise
# ----------------------------------------------------------------------

@pytest.mark.parametrize("paged", [False, True],
                         ids=["contiguous", "paged"])
@pytest.mark.parametrize("mode", ["greedy", "sampled", "spec"])
def test_chunked_matches_monolithic(mode, paged):
    cfg, model = _model()
    kw = dict(sampling="top_k", temperature=0.8) \
        if mode == "sampled" else {}
    eng = Engine(model, max_seq=64, backend="xla", **kw)
    pre = None
    skw = {}
    if paged:
        rng = np.random.RandomState(7)
        pre = rng.randint(0, cfg.vocab_size, size=(11,)).astype(np.int32)
        skw = dict(paged=True, page=8)
    if mode == "spec":
        skw["spec"] = 2
    mono = ContinuousScheduler(eng, batch=3, chunk=4, **skw).run(
        _mixed_requests(cfg, pre))
    chunked = ContinuousScheduler(eng, batch=3, chunk=4,
                                  prefill_budget=3, **skw).run(
        _mixed_requests(cfg, pre))
    _assert_same_streams(mono, chunked)


def test_chunked_budget_invariance():
    """Streams must not depend on the budget (different chunkings of
    the same prefill are the same math): budgets 1, 4 and huge (one
    chunk — degenerate monolithic-in-a-mixed-tick) all agree."""
    cfg, model = _model()
    eng = Engine(model, max_seq=64, backend="xla")
    ref = None
    for budget in (1, 4, 64):
        got = ContinuousScheduler(eng, batch=2, chunk=4,
                                  prefill_budget=budget).run(
            _mixed_requests(cfg))
        if ref is None:
            ref = got
        else:
            _assert_same_streams(ref, got)


def test_chunked_flash_backend():
    """The mixed tick through the Pallas flash kernels (per-slot
    q_lens/kv_lens masks) — small case, interpreter-priced on CPU."""
    cfg, model = _model()
    eng = Engine(model, max_seq=48, backend="flash")

    def reqs():
        rng = np.random.RandomState(4)
        return [Request(rid=i,
                        ids=rng.randint(0, cfg.vocab_size,
                                        size=(L,)).astype(np.int32),
                        gen_len=g)
                for i, (L, g) in enumerate([(5, 4), (14, 5)])]

    mono = ContinuousScheduler(eng, batch=2, chunk=2).run(reqs())
    chunked = ContinuousScheduler(eng, batch=2, chunk=2,
                                  prefill_budget=3).run(reqs())
    _assert_same_streams(mono, chunked)


# ----------------------------------------------------------------------
# the stall bound (the acceptance criterion)
# ----------------------------------------------------------------------

def test_stall_bound_under_decode_load():
    """A LONG prompt admitted into a busy decode batch: under chunked
    prefill the most prompt tokens any single poll's forward carries is
    prefill_budget (<< the prompt), where the monolithic scheduler pays
    the whole prompt inside one poll — the head-of-line stall. Live
    streams must emit on EVERY poll of the absorption window (the gap
    in scheduler ticks stays 1), and their tokens stay bitwise equal."""
    cfg, model = _model()
    eng = Engine(model, max_seq=96, backend="xla")
    rng = np.random.RandomState(5)
    live = [Request(rid=f"live{i}",
                    ids=rng.randint(0, cfg.vocab_size,
                                    size=(4,)).astype(np.int32),
                    gen_len=40)
            for i in range(2)]
    long_req = Request(
        rid="long",
        ids=rng.randint(0, cfg.vocab_size, size=(48,)).astype(np.int32),
        gen_len=4)
    budget = 6

    def run(prefill_budget):
        sched = ContinuousScheduler(eng, batch=3, chunk=1,
                                    prefill_budget=prefill_budget)
        for r in live:
            sched.submit(r)
        acc = {r.rid: [] for r in live + [long_req]}
        emitted_during = {r.rid: 0 for r in live}
        polls_during = 0
        warm = 0
        while warm < 4:                   # live slots armed + decoding
            out, _ = sched.poll()
            for rid, t in out.items():
                acc[rid].extend(t.tolist())
            warm += 1
        sched.submit(long_req)
        while "long" in [sched.slots.rids[b]
                         for b in sched.slots.prefill_slots] \
                or sched.queue_depth or not acc["long"]:
            out, done = sched.poll()
            if not acc["long"]:           # still absorbing the prompt
                polls_during += 1
                for r in live:
                    emitted_during[r.rid] += len(out.get(r.rid, ()))
            for rid, t in out.items():
                acc[rid].extend(t.tolist())
            if "long" in done and not acc["long"]:
                break
        while not sched.idle:
            out, _ = sched.poll()
            for rid, t in out.items():
                acc[rid].extend(t.tolist())
        return acc, sched.stats(), emitted_during, polls_during

    acc_c, st_c, emitted_c, polls_c = run(budget)
    acc_m, st_m, _, _ = run(None)
    # bitwise: the fairness knob must not change a single token
    for rid in acc_m:
        np.testing.assert_array_equal(np.asarray(acc_c[rid]),
                                      np.asarray(acc_m[rid]),
                                      err_msg=f"rid={rid}")
    # the bound: chunked <= budget << monolithic == full prompt
    assert st_c["max_prefill_tokens_per_poll"] <= budget, st_c
    assert st_m["max_prefill_tokens_per_poll"] == len(long_req.ids), st_m
    assert st_c["max_prefill_tokens_per_poll"] * 4 <= \
        st_m["max_prefill_tokens_per_poll"], (st_c, st_m)
    # no stalled ticks: every poll of the absorption window emitted one
    # token per live stream
    assert polls_c >= 2            # the prompt really was chunked
    for rid, n in emitted_c.items():
        assert n == polls_c, (
            f"live stream {rid} emitted {n} tokens over {polls_c} "
            f"polls while the long prompt was absorbed — chunked "
            f"prefill must not stall live streams")


# ----------------------------------------------------------------------
# composition with preemption / cancel / deadlines (mid-prefill), and
# the zero-leak invariant
# ----------------------------------------------------------------------

def _leak_check(sched):
    pool = sched.slots.prefix.pool
    assert pool.available + pool.outstanding == pool.num_pages, (
        f"page leak: {pool.available} free + {pool.outstanding} "
        f"outstanding != {pool.num_pages}")


def _uniform_requests(cfg, n=4, L=16, g=8, seed=3):
    rng = np.random.RandomState(seed)
    return [Request(rid=i,
                    ids=rng.randint(0, cfg.vocab_size,
                                    size=(L,)).astype(np.int32),
                    gen_len=g, seed=100 + i)
            for i in range(n)]


def test_preempt_mid_prefill_exact_resume():
    """A pool sized for ONE slot's worst case forces KV-pressure
    preemption while prompts are mid-prefill: streams stay bitwise
    identical to the ample-pool chunked run, and no page leaks."""
    cfg, model = _model()
    eng = Engine(model, max_seq=64, backend="xla")
    page, chunk, L, g = 8, 4, 16, 8
    worst = -(-(L + g + chunk - 1) // page)
    tiny = worst + 1 + 1
    ample = ContinuousScheduler(
        eng, batch=2, chunk=chunk, paged=True, page=page,
        prefill_budget=3).run(_uniform_requests(cfg))
    sched = ContinuousScheduler(
        eng, batch=2, chunk=chunk, paged=True, page=page,
        num_pages=tiny, prefill_budget=3)
    got = sched.run(_uniform_requests(cfg))
    assert sched.preemptions > 0, "pool was sized to force preemption"
    _assert_same_streams(ample, got)
    _leak_check(sched)


def test_preempt_targets_prefilling_slot():
    """Drive the preemption victim policy onto a slot that is ITSELF
    mid-prefill (emitted == 0 makes it the preferred victim): the
    displaced request re-queues unchanged, resumes through the prefix
    cache, and finishes bitwise identical."""
    cfg, model = _model()
    eng = Engine(model, max_seq=64, backend="xla")
    page, chunk, L, g = 8, 4, 16, 8
    worst = -(-(L + g + chunk - 1) // page)
    tiny = worst + 1 + 1
    reqs = _uniform_requests(cfg, n=2)
    ample = ContinuousScheduler(
        eng, batch=2, chunk=chunk, paged=True, page=page,
        prefill_budget=3).run(reqs)
    sched = ContinuousScheduler(
        eng, batch=2, chunk=chunk, paged=True, page=page,
        num_pages=tiny, prefill_budget=3)
    reqs = _uniform_requests(cfg, n=2)
    sched.submit(reqs[0])
    sched.poll()                          # rid 0 mid-prefill
    assert sched.slots.prefill_slots, "expected an in-progress prefill"
    sched.submit(reqs[1])                 # pool pressure -> preempt
    acc = {r.rid: [] for r in reqs}
    while not sched.idle:
        out, _ = sched.poll()
        for rid, t in out.items():
            acc[rid].extend(t.tolist())
    assert sched.preemptions > 0
    for rid in acc:
        np.testing.assert_array_equal(np.asarray(acc[rid]), ample[rid],
                                      err_msg=f"rid={rid}")
    _leak_check(sched)


def test_cancel_mid_prefill_frees_pages():
    """Cancelling a request whose prompt is still being absorbed must
    retire its slot NOW — pages freed (zero-leak), the other stream
    untouched bitwise, and only the VALID prefill extent donated to the
    radix tree (a later identical prompt must still complete
    correctly)."""
    cfg, model = _model()
    eng = Engine(model, max_seq=64, backend="xla")
    reqs = _uniform_requests(cfg, n=2)
    ample = ContinuousScheduler(
        eng, batch=2, chunk=4, paged=True, page=8,
        prefill_budget=3).run(_uniform_requests(cfg, n=2))
    sched = ContinuousScheduler(eng, batch=2, chunk=4, paged=True,
                                page=8, prefill_budget=3)
    sched.submit(reqs[0])
    sched.submit(reqs[1])
    sched.poll()                          # both mid-prefill
    assert sched.slots.prefill_slots
    assert sched.cancel(reqs[0].rid)
    acc = {r.rid: [] for r in reqs}
    while not sched.idle:
        out, _ = sched.poll()
        for rid, t in out.items():
            acc[rid].extend(t.tolist())
    assert acc[reqs[0].rid] == []         # cancelled before arming
    np.testing.assert_array_equal(np.asarray(acc[reqs[1].rid]), ample[1])
    _leak_check(sched)
    # re-submit the cancelled prompt: the donated partial extent must
    # be consistent KV (bitwise vs the ample run), not garbage
    resub = _uniform_requests(cfg, n=1)[0]
    got = sched.run([resub])
    np.testing.assert_array_equal(got[resub.rid], ample[0])
    _leak_check(sched)


def test_deadline_expiry_mid_prefill():
    """A deadline that fires while the prompt is still absorbing
    cancels the request with a visible reason (0 tokens emitted), frees
    its pages, and leaves the other stream bitwise intact."""
    import time
    cfg, model = _model()
    eng = Engine(model, max_seq=64, backend="xla")
    reqs = _uniform_requests(cfg, n=2)
    ample = ContinuousScheduler(
        eng, batch=2, chunk=4, paged=True, page=8,
        prefill_budget=2).run(_uniform_requests(cfg, n=2))
    sched = ContinuousScheduler(eng, batch=2, chunk=4, paged=True,
                                page=8, prefill_budget=2)
    doomed = Request(rid="doomed", ids=reqs[0].ids, gen_len=8,
                     seed=reqs[0].seed, deadline_ms=30.0)
    sched.submit(doomed)
    sched.submit(reqs[1])
    sched.poll()                          # both mid-prefill
    assert sched.slots.prefill_slots
    time.sleep(0.05)                      # let the deadline lapse
    acc = {"doomed": [], reqs[1].rid: []}
    while not sched.idle:
        out, _ = sched.poll()
        for rid, t in out.items():
            acc[rid].extend(t.tolist())
    assert acc["doomed"] == []
    assert sched.deadline_expired == 1
    assert "deadline_ms" in sched.rejected["doomed"]
    np.testing.assert_array_equal(np.asarray(acc[reqs[1].rid]), ample[1])
    _leak_check(sched)


def test_token_server_chunked_prefill():
    """The serving layer threads prefill_budget through to the
    scheduler: concurrent socket clients — one with a LONG prompt —
    all stream to completion with tokens bitwise equal to the
    monolithic engine serve(), and the server's stats report the
    bounded per-poll prefill."""
    import threading

    from triton_dist_tpu.serving import (ByteTokenizer, TokenServer,
                                         request_stream)

    cfg, model = _model()
    eng = Engine(model, max_seq=96, backend="xla")
    tok = ByteTokenizer(cfg.vocab_size)
    budget, gen = 5, 12
    srv = TokenServer(eng, tok, batch=3, chunk=2, paged=True, page=8,
                      prefill_budget=budget)
    th = threading.Thread(target=srv.serve_forever,
                          kwargs=dict(max_requests=3), daemon=True)
    th.start()
    prompts = ["hi", "x" * 40, "third one"]     # one LONG prompt
    results = {}

    def client(i):
        toks = []
        for msg in request_stream("127.0.0.1", srv.port, prompts[i],
                                  gen_len=gen):
            if msg.get("done"):
                assert "error" not in msg, msg
                break
            toks.extend(msg["token_ids"])
        results[i] = toks

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)
    st = srv.stats()
    srv.stop()
    th.join(timeout=60)
    assert st["prefill_budget"] == budget
    assert st["max_prefill_tokens_per_poll"] <= budget, st
    for i, p in enumerate(prompts):
        ids = np.asarray(tok.encode(p), np.int32)
        want = np.asarray(eng.serve(ids[None], gen))[0]
        np.testing.assert_array_equal(np.asarray(results[i]), want,
                                      err_msg=f"client {i}")


def test_budget_starvation_makes_progress():
    """More concurrent prefills than the per-tick budget covers: the
    FIFO split starves the younger admissions some ticks (q_len == 0 —
    no KV written, no position advanced), but everyone finishes and
    every stream is bitwise exact."""
    cfg, model = _model()
    eng = Engine(model, max_seq=64, backend="xla")
    ample = ContinuousScheduler(
        eng, batch=3, chunk=4, paged=True, page=8,
        prefill_budget=64).run(_uniform_requests(cfg, n=3))
    sched = ContinuousScheduler(eng, batch=3, chunk=4, paged=True,
                                page=8, prefill_budget=2)
    got = sched.run(_uniform_requests(cfg, n=3))
    _assert_same_streams(ample, got)
    assert sched.stats()["max_prefill_tokens_per_poll"] <= 2
    _leak_check(sched)
