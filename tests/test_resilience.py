"""Resilient serving (models/scheduler.py resilience +
runtime/chaos.py): the server must DEGRADE under pressure, never fail.

The contracts pinned here:
- KV-pressure PREEMPTION with exact resume: a pool too small for the
  offered load preempts victims (requeue + radix-tree handback)
  instead of rejecting, and every stream is BITWISE identical to the
  same workload on an ample pool — greedy, sampled, and spec=K.
- Hard rejection only when a request ALONE exceeds capacity.
- Bounded admission: max_queue overflow is a busy/retry reply, not an
  unbounded deque.
- Deadlines: expired requests are cancelled with a visible error.
- Watchdog: a hung chunk is a HANG verdict in stats() + a clean server
  shutdown, not a frozen loop.
- Chaos: malformed/oversized/disconnecting/slow clients, forced pool
  exhaustion, and drafter failures leave the server alive, leak no
  pages (available + outstanding == num_pages), and survivors' streams
  stay exact. The deterministic smoke is tier-1; the randomized soak
  is marked slow.
"""

import json
import socket
import threading
import time

import jax
import numpy as np
import pytest

from triton_dist_tpu.models import (AutoLLM, ContinuousScheduler, Engine,
                                    Request)
from triton_dist_tpu.models.config import tiny_qwen3
from triton_dist_tpu.runtime.chaos import (FaultInjector, FlakyDrafter,
                                           disconnecting_client,
                                           malformed_client,
                                           oversized_client, slow_client)

mesh1 = None
_MODELS = {}


def setup_module(module):
    global mesh1
    mesh1 = jax.make_mesh((1,), ("tp",))


def _model():
    if 1 not in _MODELS:
        cfg = tiny_qwen3(1)
        _MODELS[1] = (cfg, AutoLLM.from_config(cfg, mesh1))
    return _MODELS[1]


PAGE, CHUNK = 8, 4


def _mixed_requests(cfg, spec, seed=42, repetitive=False):
    """Deterministic request set; repetitive=True makes prompts the
    n-gram drafter can actually draft from (spec=K coverage)."""
    rng = np.random.RandomState(seed)
    out = []
    if repetitive:
        pat = rng.randint(0, cfg.vocab_size, size=(4,))
    for i, (L, g) in enumerate(spec):
        ids = (np.tile(pat, -(-L // 4))[:L] if repetitive
               else rng.randint(0, cfg.vocab_size, size=(L,)))
        out.append(Request(rid=i, ids=ids.astype(np.int32), gen_len=g,
                           seed=100 + i))
    return out


def _small_pool(cfg, max_prompt, max_gen):
    """Pages for ONE worst-case slot (+ trash + one spare page): with
    batch 2+ this guarantees pool pressure, and any single request of
    the workload still fits alone — preemption, not rejection."""
    worst = -(-(max_prompt + max_gen + CHUNK - 1) // PAGE)
    return worst + 1 + 1


def _assert_no_leak(sched):
    """The chaos invariant: after the scheduler drains, every page is
    free XOR outstanding, and once the tree lets go nothing is held."""
    pool = sched.slots.prefix.pool
    assert pool.available + pool.outstanding == pool.num_pages
    assert not sched.slots.occupied
    sched.slots.prefix.tree.evict_until(10 ** 9)
    assert pool.pages_in_use == 0, "leaked page refs"
    assert pool.available == pool.num_pages - 1    # trash stays reserved


# ----------------------------------------------------------------------
# preemption with exact resume
# ----------------------------------------------------------------------


def _run_small_vs_ample(eng, cfg, reqs_fn, *, spec=0, drafter=None,
                        prefix_cache=True):
    max_p = max(len(r.ids) for r in reqs_fn())
    max_g = max(r.gen_len for r in reqs_fn())
    runs, preempts = {}, 0
    for label, npages in (("small", _small_pool(cfg, max_p, max_g)),
                          ("ample", None)):
        sched = ContinuousScheduler(
            eng, batch=2, chunk=CHUNK, paged=True,
            prefix_cache=prefix_cache, page=PAGE, num_pages=npages,
            spec=spec, drafter=drafter)
        runs[label] = sched.run(reqs_fn())
        if label == "small":
            preempts = sched.preemptions
            assert not sched.rejected, sched.rejected
            _assert_no_leak(sched)
    assert preempts > 0, "pool sizing failed to force preemption"
    for r in reqs_fn():
        np.testing.assert_array_equal(
            runs["small"][r.rid], runs["ample"][r.rid],
            err_msg=f"rid={r.rid}: preempted stream diverged")
        assert len(runs["small"][r.rid]) == r.gen_len
    return runs["small"]


def test_preempt_resume_greedy_bitwise():
    """Preemption forced (pool fits ~1 worst-case slot, batch=2) vs
    disabled-by-ample-pool: greedy streams bitwise identical, every
    request completes, zero leaks — and vs Engine.serve() too (resume
    is invisible end to end, not merely self-consistent)."""
    cfg, model = _model()
    eng = Engine(model, max_seq=64, backend="xla")
    got = _run_small_vs_ample(
        eng, cfg, lambda: _mixed_requests(
            cfg, [(10, 12), (14, 10), (7, 9)]))
    for r in _mixed_requests(cfg, [(10, 12), (14, 10), (7, 9)]):
        want = np.asarray(eng.serve(np.tile(r.ids[None], (2, 1)),
                                    r.gen_len))[0]
        np.testing.assert_array_equal(got[r.rid], want,
                                      err_msg=f"rid={r.rid}")


def test_preempt_resume_sampled_bitwise():
    """Sampled mode: the ResumeState PRNG-key snapshot must continue
    each slot's chain exactly — preempted streams equal the ample-pool
    run AND a batch-1 serve() at the slot's seed."""
    cfg, model = _model()
    eng = Engine(model, max_seq=64, backend="xla", sampling="top_k",
                 temperature=0.8)
    got = _run_small_vs_ample(
        eng, cfg, lambda: _mixed_requests(
            cfg, [(10, 12), (14, 10), (7, 9)]))
    for r in _mixed_requests(cfg, [(10, 12), (14, 10), (7, 9)]):
        want = np.asarray(eng.serve(r.ids[None], r.gen_len,
                                    seed=r.seed))[0]
        np.testing.assert_array_equal(got[r.rid], want,
                                      err_msg=f"rid={r.rid}")


def test_preempt_resume_spec_greedy_bitwise():
    """Preemption composes with spec=K: the pending seed token is
    restored (not re-drawn) and the drafter corpus is the resumed
    ids, so spec streams under preemption equal the ample-pool run."""
    cfg, model = _model()
    eng = Engine(model, max_seq=64, backend="xla")
    _run_small_vs_ample(
        eng, cfg, lambda: _mixed_requests(
            cfg, [(12, 12), (16, 10), (8, 9)], repetitive=True),
        spec=2)


def test_preempt_resume_sampled_spec_bitwise():
    """spec=K + sampled + preemption: the rejection-sampling key chain
    survives the preempt/resume round-trip bitwise."""
    cfg, model = _model()
    eng = Engine(model, max_seq=64, backend="xla", sampling="top_k",
                 temperature=0.8)
    _run_small_vs_ample(
        eng, cfg, lambda: _mixed_requests(
            cfg, [(12, 12), (16, 10), (8, 9)], repetitive=True),
        spec=2)


def test_preempt_resume_cache_off_recompute():
    """prefix_cache=False is pure vLLM-style recompute preemption (no
    tree handback — the freed pages recycle immediately and resume
    re-prefills everything): still bitwise."""
    cfg, model = _model()
    eng = Engine(model, max_seq=64, backend="xla")
    max_p, max_g = 14, 12
    runs = {}
    for label, npages in (("small", _small_pool(cfg, max_p, max_g)),
                          ("ample", None)):
        sched = ContinuousScheduler(
            eng, batch=2, chunk=CHUNK, paged=True, prefix_cache=False,
            page=PAGE, num_pages=npages)
        runs[label] = sched.run(_mixed_requests(
            cfg, [(10, 12), (14, 10), (7, 9)]))
        if label == "small":
            assert sched.preemptions > 0
    for r in _mixed_requests(cfg, [(10, 12), (14, 10), (7, 9)]):
        np.testing.assert_array_equal(runs["small"][r.rid],
                                      runs["ample"][r.rid],
                                      err_msg=f"rid={r.rid}")


def test_hard_reject_only_when_alone_exceeds_capacity():
    """A request whose worst-case footprint exceeds the WHOLE pool is
    hard-rejected UPFRONT — without thrashing the live slots through
    pointless preemptions (a repeated never-fits request must not be a
    denial-of-service amplifier) — while the small request streams on
    undisturbed."""
    cfg, model = _model()
    eng = Engine(model, max_seq=96, backend="xla")
    rng = np.random.RandomState(3)
    small = Request(rid="small", ids=rng.randint(
        0, cfg.vocab_size, size=(8,)).astype(np.int32), gen_len=6)
    # pool sized for the small request only; "big" fits the SLOT
    # (max_seq) but never the pool, even with every victim preempted
    num_pages = _small_pool(cfg, 8, 6)
    big = Request(rid="big", ids=rng.randint(
        0, cfg.vocab_size,
        size=(num_pages * PAGE,)).astype(np.int32), gen_len=8)
    sched = ContinuousScheduler(eng, batch=2, chunk=CHUNK, paged=True,
                                prefix_cache=True, page=PAGE,
                                num_pages=num_pages)
    got = sched.run([small, big])
    assert len(got["big"]) == 0
    assert "page pool exhausted" in sched.rejected["big"]
    assert sched.preemptions == 0, \
        "never-fits request must not thrash live slots"
    want = np.asarray(eng.serve(np.tile(small.ids[None], (2, 1)), 6))[0]
    np.testing.assert_array_equal(got["small"], want)
    _assert_no_leak(sched)


def test_preempt_disabled_keeps_old_rejection():
    """preempt=False restores the hard-reject contract (the
    differential baseline): pool exhaustion with a victim present
    rejects instead of preempting."""
    cfg, model = _model()
    eng = Engine(model, max_seq=64, backend="xla")
    rng = np.random.RandomState(6)
    ids = rng.randint(0, cfg.vocab_size, size=(2, 20)).astype(np.int32)
    sched = ContinuousScheduler(eng, batch=2, chunk=CHUNK, paged=True,
                                prefix_cache=True, page=PAGE,
                                num_pages=_small_pool(cfg, 20, 6),
                                preempt=False)
    got = sched.run([Request(rid=i, ids=ids[i], gen_len=6)
                     for i in range(2)])
    lens = sorted(len(got[i]) for i in range(2))
    assert lens == [0, 6], lens
    assert sched.preemptions == 0
    assert any("page pool exhausted" in v for v in
               sched.rejected.values())


# ----------------------------------------------------------------------
# backpressure, deadlines, watchdog, rejection bookkeeping
# ----------------------------------------------------------------------


def test_max_queue_backpressure():
    """submit() refuses (returns False, nothing queued) past max_queue;
    internal preemption re-queues bypass the bound."""
    cfg, model = _model()
    eng = Engine(model, max_seq=64, backend="xla")
    sched = ContinuousScheduler(eng, batch=1, chunk=CHUNK, max_queue=2)
    rng = np.random.RandomState(0)
    mk = lambda i: Request(rid=i, ids=rng.randint(
        0, cfg.vocab_size, size=(4,)).astype(np.int32), gen_len=4)
    assert sched.submit(mk(0)) and sched.submit(mk(1))
    assert not sched.submit(mk(2))
    assert sched.queue_depth == 2
    assert sched.stats()["busy_rejections"] == 1
    while not sched.idle:
        sched.poll()
    assert sched.submit(mk(3))          # drained line accepts again


def test_deadline_expires_queued_and_inflight():
    """deadline_ms=0 expires before admission; an in-flight slot whose
    deadline passes mid-decode is cancelled with a token-count reason.
    Survivors stream exactly."""
    cfg, model = _model()
    eng = Engine(model, max_seq=64, backend="xla")
    rng = np.random.RandomState(1)
    ids = rng.randint(0, cfg.vocab_size, size=(3, 6)).astype(np.int32)
    sched = ContinuousScheduler(eng, batch=2, chunk=CHUNK, paged=True,
                                prefix_cache=True, page=PAGE)
    sched.submit(Request(rid="dead", ids=ids[0], gen_len=8,
                         deadline_ms=0.0))
    sched.submit(Request(rid="ok", ids=ids[1], gen_len=8))
    acc = []
    while not sched.idle:
        out, done = sched.poll()
        acc.extend(out.get("ok", []))
        assert "dead" not in out
    assert "expired before admission" in sched.rejected["dead"]
    assert sched.deadline_expired == 1
    want = np.asarray(eng.serve(np.tile(ids[1][None], (2, 1)), 8))[0]
    np.testing.assert_array_equal(np.asarray(acc), want)
    # in-flight expiry: admit, let one chunk run, then force the clock
    sched.submit(Request(rid="mid", ids=ids[2], gen_len=40,
                         deadline_ms=1e6))
    out, done = sched.poll()
    assert len(out["mid"]) == CHUNK and "mid" not in done
    sched._deadline["mid"] = 0.0              # deterministic expiry
    out, done = sched.poll()
    assert "mid" in done
    assert f"exceeded after {CHUNK} tokens" in sched.rejected["mid"]
    _assert_no_leak(sched)


def test_cross_thread_submit_with_deadlines():
    """The class contract — enqueue from ANY thread, one driver thread
    polls — must hold now that submit() stamps the deadline dict:
    concurrent submits during _expire_deadlines' iteration must not
    blow up poll() (regression: 'dict changed size during iteration')
    and every request must drain."""
    cfg, model = _model()
    eng = Engine(model, max_seq=64, backend="xla")
    sched = ContinuousScheduler(eng, batch=2, chunk=CHUNK)
    rng = np.random.RandomState(9)
    ids = rng.randint(0, cfg.vocab_size, size=(4,)).astype(np.int32)
    stop = threading.Event()
    counts = {}

    def producer(k):
        i = 0
        while not stop.is_set():
            sched.submit(Request(
                rid=(k, i), ids=ids, gen_len=2,
                deadline_ms=0.01 if i % 10 == 0 else 1e6))
            counts[k] = i = i + 1
            time.sleep(0.002)

    prods = [threading.Thread(target=producer, args=(k,))
             for k in range(3)]
    for p in prods:
        p.start()
    t_end = time.monotonic() + 2.5
    while time.monotonic() < t_end:
        sched.poll()
    stop.set()
    for p in prods:
        p.join(timeout=30)
    while not sched.idle:
        sched.poll()
    assert sum(counts.values()) > 50
    assert not sched._deadline, "deadline bookkeeping leaked"


def test_watchdog_hang_verdict_in_stats():
    """A chunk that outlives watchdog_s raises HangError and leaves a
    HANG verdict in stats() — the loop never silently freezes."""
    from triton_dist_tpu.runtime.stress import HangError
    cfg, model = _model()
    eng = Engine(model, max_seq=64, backend="xla")
    # generous budget first: the opening chunk INCLUDES the XLA
    # compile, which is exactly why the deadline is configurable
    sched = ContinuousScheduler(eng, batch=1, chunk=CHUNK,
                                watchdog_s=120.0)
    rng = np.random.RandomState(2)
    sched.submit(Request(rid=0, ids=rng.randint(
        0, cfg.vocab_size, size=(4,)).astype(np.int32), gen_len=8))
    sched.poll()                                  # healthy chunk first
    sched.watchdog_s = 0.25
    sched.slots.step_chunk = lambda chunk: time.sleep(30.0)
    with pytest.raises(HangError) as ei:
        sched.poll()
    assert "HANG" in str(ei.value) and ei.value.label is not None
    assert "HANG" in sched.stats()["hang"]


def test_rejected_bookkeeping_bounded_at_1024():
    """The rejected side-channel must not leak on callers that never
    read reasons: >1024 entries evict oldest-first (satellite — the
    eviction path had no direct test)."""
    cfg, model = _model()
    eng = Engine(model, max_seq=48, backend="xla")
    sched = ContinuousScheduler(eng, batch=4, chunk=CHUNK)
    # over-capacity requests are rejected before any device work
    bad_ids = np.zeros((200,), np.int32)
    n = 1100
    for i in range(n):
        sched.submit(Request(rid=i, ids=bad_ids, gen_len=200))
    seen = []
    while not sched.idle:
        _, done = sched.poll()
        seen.extend(done)
    assert len(seen) == n
    assert len(sched.rejected) == 1024
    assert 0 not in sched.rejected and n - 1 in sched.rejected
    assert min(sched.rejected) == n - 1024        # oldest evicted first


# ----------------------------------------------------------------------
# chaos: drafter faults, forced exhaustion
# ----------------------------------------------------------------------


def test_flaky_drafter_streams_stay_exact():
    """A drafter that raises (and one that babbles out-of-vocab
    garbage) must degrade to plain decode for that window: streams stay
    bitwise equal to spec=0 and stats counts the failures."""
    from triton_dist_tpu.models.spec_decode import NgramDrafter
    cfg, model = _model()
    eng = Engine(model, max_seq=64, backend="xla")
    reqs = lambda: _mixed_requests(cfg, [(12, 10), (8, 9)],
                                   repetitive=True)
    base = ContinuousScheduler(eng, batch=2, chunk=CHUNK)
    want = base.run(reqs())
    for garbage in (False, True):
        flaky = FlakyDrafter(NgramDrafter(), fail_every=2,
                             garbage=garbage)
        sched = ContinuousScheduler(eng, batch=2, chunk=CHUNK, spec=2,
                                    drafter=flaky)
        got = sched.run(reqs())
        assert sched.stats()["drafter_errors"] > 0
        assert flaky.failures > 0
        for r in reqs():
            np.testing.assert_array_equal(
                got[r.rid], want[r.rid],
                err_msg=f"garbage={garbage} rid={r.rid}")


def test_fault_injector_forces_preemption_invisibly():
    """Forced PoolExhausted on an AMPLE pool exercises the full
    preempt/requeue/resume machinery with zero real pressure — and the
    streams must not notice. Attempt 1 hits while the only resident is
    fresh (no ELIGIBLE victim — the chunked-prefill liveness gate) so
    the admission WAITS a poll; attempt 2 hits after that resident
    decoded a chunk, so it is preempted."""
    cfg, model = _model()
    eng = Engine(model, max_seq=64, backend="xla")
    reqs = lambda: _mixed_requests(cfg, [(10, 10), (9, 8), (7, 9)])
    clean = ContinuousScheduler(eng, batch=2, chunk=CHUNK, paged=True,
                                prefix_cache=True, page=PAGE)
    want = clean.run(reqs())
    fault = FaultInjector(exhaust_admissions=(1, 2))
    sched = ContinuousScheduler(eng, batch=2, chunk=CHUNK, paged=True,
                                prefix_cache=True, page=PAGE,
                                fault=fault)
    got = sched.run(reqs())
    assert fault.injected["pool_exhausted"] == 2
    assert sched.preemptions >= 1
    for r in reqs():
        np.testing.assert_array_equal(got[r.rid], want[r.rid],
                                      err_msg=f"rid={r.rid}")
    _assert_no_leak(sched)


def test_chaos_host_tier_exhaustion_no_leak():
    """Tier-1 chaos smoke for the HOST KV TIER (models/kv_tier.py): a
    pressure-sized device pool over a host pool that is BOTH
    chaos-refused (FaultInjector.host_demotion) and genuinely tiny, so
    demotions, promotions, true drops from host LRU, AND fault-forced
    drops all fire in one workload. The server-side invariants: every
    stream bitwise equal to the tierless cache-off run, and the
    cross-tier zero-leak invariant — device
    ``available + outstanding == num_pages`` AND host
    ``pages_resident == sum(entries) <= capacity`` — after the dust
    settles."""
    cfg, model = _model()
    eng = Engine(model, max_seq=64, backend="xla")
    reqs = lambda: _mixed_requests(cfg, [(20, 8), (18, 6), (21, 7),
                                         (20, 5), (18, 6)])
    base = ContinuousScheduler(eng, batch=2, chunk=CHUNK, paged=True,
                               prefix_cache=False, page=PAGE)
    want = base.run(reqs())
    fault = FaultInjector(exhaust_host_demotions=(1, 2))
    sched = ContinuousScheduler(
        eng, batch=2, chunk=CHUNK, paged=True, prefix_cache=True,
        page=PAGE, num_pages=_small_pool(cfg, 21, 8) + 1,
        host_pool_pages=6, fault=fault)
    got = sched.run(reqs())
    st = sched.stats()
    assert st["demotions"] > 0, st
    assert fault.injected["host_exhausted"] >= 1
    assert st["evictions"] > 0, st       # fault-forced true drops ran
    for r in reqs():
        np.testing.assert_array_equal(got[r.rid], want[r.rid],
                                      err_msg=f"rid={r.rid}")
    _assert_no_leak(sched)
    hp = sched.slots.prefix.host
    assert hp.pages_resident == sum(
        e.n_pages for e in hp._entries.values())
    assert hp.pages_resident <= hp.capacity
    assert set(sched.slots.prefix.tree._host_nodes) == \
        set(hp._entries)


# ----------------------------------------------------------------------
# socket-level chaos against a live TokenServer
# ----------------------------------------------------------------------


def _start_server(eng, cfg, **kw):
    from triton_dist_tpu.serving import ByteTokenizer, TokenServer
    tok = ByteTokenizer(cfg.vocab_size)
    srv = TokenServer(eng, tok, **kw)
    th = threading.Thread(target=srv.serve_forever, daemon=True)
    th.start()
    return srv, th, tok


def test_malformed_and_oversized_requests_get_structured_errors():
    """Garbage JSON and a 1 MiB request 'line' both get a
    {"done": true, "error": ...} refusal (satellite: the reader used to
    print to stderr and slam the socket), and the server keeps serving
    a well-formed client afterwards."""
    cfg, model = _model()
    eng = Engine(model, max_seq=64, backend="xla")
    srv, th, tok = _start_server(eng, cfg, batch=1, chunk=CHUNK)
    try:
        bad = malformed_client("127.0.0.1", srv.port)
        assert bad is not None and bad.get("done"), bad
        assert "bad request" in bad["error"], bad
        big = oversized_client("127.0.0.1", srv.port, nbytes=1 << 20)
        assert big is not None and "exceeds" in big["error"], big
        # non-dict JSON is refused too (json.loads succeeds on it)
        arr = malformed_client("127.0.0.1", srv.port, b'[1, 2, 3]\n')
        assert arr is not None and "JSON object" in arr["error"], arr
        # invalid UTF-8 poisons the text-mode read side; the reply
        # side must still deliver a refusal (regression: this used to
        # kill the reader thread and leave the client hanging)
        utf = malformed_client("127.0.0.1", srv.port,
                               b'\xff\xfe{"prompt": "x"}\n')
        assert utf is not None and "UTF-8" in utf["error"], utf
        from triton_dist_tpu.serving import request_stream
        got = []
        for msg in request_stream("127.0.0.1", srv.port, "still alive",
                                  gen_len=6):
            if msg.get("done"):
                assert "error" not in msg, msg
                break
            got.extend(msg["token_ids"])
        ids = np.asarray(tok.encode("still alive"), np.int32)
        want = np.asarray(eng.serve(ids[None], 6))[0]
        np.testing.assert_array_equal(np.asarray(got), want)
    finally:
        srv.stop()
        th.join(timeout=60)


def test_server_busy_reply_and_client_retry():
    """One slot occupied by a hog + a parked client filling the
    max_queue=1 waiting line: the next client gets
    {"busy": true, "retry_after_ms": ...}; request_stream's bounded
    retry then completes once the hog hangs up and the line drains."""
    cfg, model = _model()
    eng = Engine(model, max_seq=256, backend="xla")
    srv, th, tok = _start_server(eng, cfg, batch=1, chunk=2,
                                 max_queue=1)
    try:
        # hog: a long request occupying the single slot
        s = socket.create_connection(("127.0.0.1", srv.port),
                                     timeout=60)
        f = s.makefile("rw")
        f.write(json.dumps({"prompt": "hog", "gen_len": 150}) + "\n")
        f.flush()
        assert json.loads(f.readline()).get("token_ids")
        # parked: fills the 1-deep waiting line (stays connected)
        parked = socket.create_connection(("127.0.0.1", srv.port),
                                          timeout=60)
        pkf = parked.makefile("rw")
        pkf.write(json.dumps({"prompt": "parked", "gen_len": 4}) + "\n")
        pkf.flush()
        for _ in range(500):            # reader threads are async
            if srv.sched.queue_depth >= 1:
                break
            time.sleep(0.01)
        assert srv.sched.queue_depth >= 1
        # raw probe: the busy reply is structured, with a retry hint
        probe = socket.create_connection(("127.0.0.1", srv.port),
                                         timeout=60)
        pf = probe.makefile("rw")
        pf.write(json.dumps({"prompt": "probe", "gen_len": 4}) + "\n")
        pf.flush()
        reply = json.loads(pf.readline())
        assert reply.get("busy") and reply["retry_after_ms"] > 0, reply
        probe.close()
        # retrying client: dropping the hog frees the slot mid-retry
        from triton_dist_tpu.serving import request_stream
        got = []
        stream = request_stream("127.0.0.1", srv.port, "patient",
                                gen_len=6, busy_retries=500)
        f.close()
        s.close()                     # hog hangs up -> slot cancels
        for msg in stream:
            if msg.get("done"):
                assert "error" not in msg, msg
                break
            got.extend(msg["token_ids"])
        ids = np.asarray(tok.encode("patient"), np.int32)
        want = np.asarray(eng.serve(ids[None], 6))[0]
        np.testing.assert_array_equal(np.asarray(got), want)
        assert srv.stats()["busy_rejections"] >= 1
        pkf.close()
        parked.close()
    finally:
        srv.stop()
        th.join(timeout=60)


def test_server_reports_scheduler_rejection_reason():
    """TokenServer._finish plumbing (satellite): a scheduler-rejected
    request's reason must reach the client's done message — here a
    request that alone exceeds the pool (no victim to preempt)."""
    cfg, model = _model()
    eng = Engine(model, max_seq=96, backend="xla")
    num_pages = _small_pool(cfg, 8, 6)
    srv, th, tok = _start_server(eng, cfg, batch=2, chunk=CHUNK,
                                 paged=True, prefix_cache=True,
                                 page=PAGE, num_pages=num_pages)
    try:
        from triton_dist_tpu.serving import request_stream
        # ~64 prompt tokens: fits the slot (capacity 93) but needs more
        # groups than the whole pool holds
        msgs = list(request_stream("127.0.0.1", srv.port, "x" * 64,
                                   gen_len=6))
        assert msgs and msgs[-1].get("done"), msgs
        assert "page pool exhausted" in msgs[-1].get("error", ""), \
            msgs[-1]
        assert msgs[-1]["n_tokens"] == 0
    finally:
        srv.stop()
        th.join(timeout=60)


def test_server_deadline_reported_to_client():
    """A deadline_ms=0 request gets a done message whose error names
    the deadline — not a success-shaped empty stream."""
    cfg, model = _model()
    eng = Engine(model, max_seq=64, backend="xla")
    srv, th, tok = _start_server(eng, cfg, batch=1, chunk=CHUNK)
    try:
        from triton_dist_tpu.serving import request_stream
        msgs = list(request_stream("127.0.0.1", srv.port, "too slow",
                                   gen_len=6, deadline_ms=0.0))
        assert msgs and msgs[-1].get("done"), msgs
        assert "deadline" in msgs[-1].get("error", ""), msgs[-1]
    finally:
        srv.stop()
        th.join(timeout=60)


@pytest.mark.parametrize("kw,wedged", [
    # dispatch-ahead: a dispatch cannot hang, the readback can
    ({}, ("land", lambda: time.sleep(30.0))),
    # the control: the synchronous tick, dispatch and readback in one
    (dict(overlap=False), ("step_chunk", lambda chunk: time.sleep(30.0)))],
    ids=["default", "sync"])
def test_server_hang_ends_with_error_not_freeze(kw, wedged):
    """A hung decode chunk (watchdog_s) must end serve_forever with a
    structured HANG error to the live client instead of freezing."""
    cfg, model = _model()
    eng = Engine(model, max_seq=64, backend="xla")
    srv, th, tok = _start_server(eng, cfg, batch=1, chunk=CHUNK,
                                 watchdog_s=120.0, **kw)
    try:
        # healthy first so programs are warm (the opening chunk pays
        # the XLA compile), then tighten the deadline and wedge
        from triton_dist_tpu.serving import request_stream
        list(request_stream("127.0.0.1", srv.port, "warm", gen_len=4))
        srv.sched.watchdog_s = 0.25
        setattr(srv.sched.slots, *wedged)
        msgs = list(request_stream("127.0.0.1", srv.port, "doomed",
                                   gen_len=8, timeout=30.0))
        assert msgs and msgs[-1].get("done"), msgs
        assert "HANG" in msgs[-1].get("error", ""), msgs[-1]
        th.join(timeout=30)
        assert not th.is_alive(), "server loop froze instead of exiting"
        assert "HANG" in srv.stats()["hang"]
    finally:
        srv.stop()
        th.join(timeout=60)


def test_chaos_smoke_deterministic():
    """The tier-1 chaos smoke: a tiny pool + a fixed cast of abusive
    clients (malformed, oversized, mid-stream disconnect, slow-to-send,
    deadline-0) around well-behaved survivors. The server must complete
    every survivor bitwise-exactly, reply to every abuser, leak zero
    pages, and keep its loop alive."""
    cfg, model = _model()
    eng = Engine(model, max_seq=96, backend="xla")
    num_pages = _small_pool(cfg, 24, 12)
    srv, th, tok = _start_server(eng, cfg, batch=2, chunk=CHUNK,
                                 paged=True, prefix_cache=True,
                                 page=PAGE, num_pages=num_pages)
    from triton_dist_tpu.serving import request_stream
    survivors = {"surv-A": ("a calm client", 10),
                 "surv-B": ("another calm one", 12)}
    results = {}

    def survivor(name):
        prompt, gen = survivors[name]
        toks = []
        for msg in request_stream("127.0.0.1", srv.port, prompt,
                                  gen_len=gen, busy_retries=100):
            if msg.get("done"):
                results[name] = (toks, msg)
                return
            toks.extend(msg["token_ids"])

    try:
        threads = [threading.Thread(target=survivor, args=(n,))
                   for n in survivors]
        for t in threads:
            t.start()
        # the abuse, interleaved with the survivors' streams
        assert "bad request" in malformed_client(
            "127.0.0.1", srv.port)["error"]
        assert "exceeds" in oversized_client(
            "127.0.0.1", srv.port, nbytes=1 << 18)["error"]
        dropped = disconnecting_client("127.0.0.1", srv.port,
                                       "rude client", gen_len=24,
                                       after_chunks=1)
        assert dropped, "disconnector saw no tokens before hanging up"
        msgs = list(request_stream("127.0.0.1", srv.port, "hopeless",
                                   gen_len=8, deadline_ms=0.0,
                                   busy_retries=100))
        assert "deadline" in msgs[-1].get("error", ""), msgs[-1]
        s_toks, s_done = slow_client("127.0.0.1", srv.port,
                                     "slow but honest", gen_len=6,
                                     delay_s=0.2)
        assert s_done is not None and "error" not in s_done
        for t in threads:
            t.join(timeout=600)
        assert th.is_alive(), "server loop died under chaos"
        for name, (prompt, gen) in survivors.items():
            toks, done_msg = results[name]
            assert "error" not in done_msg, (name, done_msg)
            ids = np.asarray(tok.encode(prompt), np.int32)
            want = np.asarray(eng.serve(np.tile(ids[None], (2, 1)),
                                        gen))[0]
            np.testing.assert_array_equal(np.asarray(toks), want,
                                          err_msg=name)
        ids = np.asarray(tok.encode("slow but honest"), np.int32)
        want = np.asarray(eng.serve(np.tile(ids[None], (2, 1)), 6))[0]
        np.testing.assert_array_equal(np.asarray(s_toks), want)
    finally:
        srv.stop()
        th.join(timeout=60)
    # no leaks once the dust settles
    st = srv.stats()
    assert st["pages_free"] + st["pages_outstanding"] == num_pages, st
    pool = srv.sched.slots.prefix.pool
    srv.sched.slots.prefix.tree.evict_until(10 ** 9)
    assert pool.pages_in_use == 0
    assert pool.available == num_pages - 1


@pytest.mark.slow
def test_chaos_soak_randomized():
    """The long randomized soak (slow tier): ~40 seeded-random clients
    — good, malformed, oversized, disconnecting, deadline-bound — fired
    at a pressure-sized pool with forced-exhaustion injections. End
    state: loop alive, zero page leaks, every well-behaved client's
    stream bitwise exact."""
    cfg, model = _model()
    eng = Engine(model, max_seq=96, backend="xla")
    num_pages = _small_pool(cfg, 20, 12)
    fault = FaultInjector(exhaust_admissions=(3, 9, 17))
    srv, th, tok = _start_server(eng, cfg, batch=2, chunk=CHUNK,
                                 paged=True, prefix_cache=True,
                                 page=PAGE, num_pages=num_pages,
                                 fault=fault)
    from triton_dist_tpu.serving import request_stream
    rng = np.random.RandomState(0)
    results = {}

    def good(i, prompt, gen):
        toks = []
        try:
            for msg in request_stream("127.0.0.1", srv.port, prompt,
                                      gen_len=gen, busy_retries=200):
                if msg.get("done"):
                    results[i] = (prompt, gen, toks, msg)
                    return
                toks.extend(msg["token_ids"])
            results[i] = (prompt, gen, toks, None)
        except Exception as e:          # noqa: BLE001 - recorded, asserted below
            results[i] = (prompt, gen, toks, e)

    threads = []
    try:
        for i in range(40):
            kind = rng.rand()
            prompt = "client %d says %d" % (i, rng.randint(1000))
            gen = int(rng.randint(4, 13))
            if kind < 0.45:
                t = threading.Thread(target=good,
                                     args=(i, prompt, gen))
                t.start()
                threads.append(t)
            elif kind < 0.6:
                malformed_client("127.0.0.1", srv.port)
            elif kind < 0.7:
                oversized_client("127.0.0.1", srv.port,
                                 nbytes=1 << 17)
            elif kind < 0.85:
                disconnecting_client("127.0.0.1", srv.port, prompt,
                                     gen_len=24, after_chunks=1)
            else:
                list(request_stream("127.0.0.1", srv.port, prompt,
                                    gen_len=gen, deadline_ms=0.0,
                                    busy_retries=200))
            if rng.rand() < 0.3:
                time.sleep(0.02)
        for t in threads:
            t.join(timeout=600)
        assert th.is_alive(), "server loop died during the soak"
        assert results, "soak produced no well-behaved clients"
        for i, (prompt, gen, toks, done_msg) in results.items():
            assert isinstance(done_msg, dict), (i, done_msg)
            assert "error" not in done_msg, (i, done_msg)
            ids = np.asarray(tok.encode(prompt), np.int32)
            want = np.asarray(eng.serve(np.tile(ids[None], (2, 1)),
                                        gen))[0]
            np.testing.assert_array_equal(np.asarray(toks), want,
                                          err_msg=f"client {i}")
    finally:
        srv.stop()
        th.join(timeout=120)
    pool = srv.sched.slots.prefix.pool
    assert pool.available + pool.outstanding == num_pages
    srv.sched.slots.prefix.tree.evict_until(10 ** 9)
    assert pool.pages_in_use == 0
    assert pool.available == num_pages - 1


# ----------------------------------------------------------------------
# SLO-aware preemption-victim choice (models/scheduler.py + fleet PR)
# ----------------------------------------------------------------------

def _slo_victim_scenario(slos):
    """Interleaved-admission preemption rig: A (slos[0]) is admitted
    first and has emitted MORE tokens than B (slos[1]) by the time C
    (slos[2]) arrives at a free slot under a chaos-forced
    PoolExhausted — so the old victim-blind key (fewest generated)
    always evicts B, and any other choice is the SLO rank at work. The
    victim re-queues and re-admits within the same poll, so it is
    identified by its traced "preempt" req_event. Returns (streams,
    the preempted rids)."""
    import dataclasses as _dc
    cfg, model = _model()
    eng = Engine(model, max_seq=64, backend="xla")
    base = _mixed_requests(cfg, [(10, 24), (8, 24), (7, 6)])
    reqs = [_dc.replace(r, slo=s) for r, s in zip(base, slos)]
    # admission ATTEMPTS: A=0, B=1, C=2 (chaos) -> preempt ->
    # C retry=3 -> victim re-admit=4
    fault = FaultInjector(exhaust_admissions=(2,))
    sched = ContinuousScheduler(eng, batch=3, chunk=CHUNK, paged=True,
                                prefix_cache=True, page=PAGE,
                                fault=fault, trace=True)
    acc = {r.rid: [] for r in reqs}

    def polls(n):
        for _ in range(n):
            out, _ = sched.poll()
            for rid, toks in out.items():
                acc[rid].extend(np.asarray(toks).tolist())

    sched.submit(reqs[0])
    polls(2)                      # A armed + emitting
    sched.submit(reqs[1])
    polls(2)                      # B armed + emitting; A well ahead
    slots = sched.slots
    b_a = slots.rids.index(0)
    b_b = slots.rids.index(1)
    assert slots.emitted(b_a) > slots.emitted(b_b) > 0, \
        "rig broke: A must lead B with both victim-eligible"
    sched.submit(reqs[2])
    polls(1)                      # attempt 2: PoolExhausted -> preempt
    assert fault.injected["pool_exhausted"] == 1
    assert sched.preemptions == 1
    while not sched.idle:
        polls(1)
    _assert_no_leak(sched)
    preempted = {
        str(rid) for rid, rec in
        sched.tele.export().get("requests", {}).items()
        if any("preempt" in str(ev)
               for ev in rec.get("events", []))}
    return {rid: np.asarray(t, np.int32)
            for rid, t in acc.items()}, preempted


def test_slo_victim_batch_preempted_before_interactive():
    """Under pool pressure the BATCH-class resident is the preemption
    victim even though the interactive one has generated fewer tokens
    (the victim-blind key would have evicted it) — and the preempted
    stream still resumes to bitwise completion."""
    cfg, model = _model()
    eng = Engine(model, max_seq=64, backend="xla")
    clean = ContinuousScheduler(eng, batch=3, chunk=CHUNK, paged=True,
                                prefix_cache=True, page=PAGE)
    want = clean.run(_mixed_requests(cfg, [(10, 24), (8, 24), (7, 6)]))
    got, preempted = _slo_victim_scenario(
        ("batch", "interactive", "interactive"))
    assert preempted == {"0"}, \
        f"victim must be the batch-class A, got {preempted}"
    for rid, w in want.items():
        np.testing.assert_array_equal(got[rid], w,
                                      err_msg=f"rid={rid}")


def test_slo_victim_uniform_classes_degenerate_to_blind_bitwise():
    """Uniform classes make the SLO rank a constant leading key: the
    victim choice (and therefore every stream, bitwise) must equal the
    victim-blind baseline — asserted against the UNTAGGED run, which
    is the pre-SLO scheduler verbatim."""
    got_blind, preempted_blind = _slo_victim_scenario(
        (None, None, None))
    got_uniform, preempted_uniform = _slo_victim_scenario(
        ("batch", "batch", "batch"))
    # fewest-generated picks B in both arms
    assert preempted_blind == preempted_uniform == {"1"}
    assert set(got_blind) == set(got_uniform)
    for rid, w in got_blind.items():
        np.testing.assert_array_equal(got_uniform[rid], w,
                                      err_msg=f"rid={rid}")
