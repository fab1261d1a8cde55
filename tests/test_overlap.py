"""Overlap scheduler (dispatch-ahead host loop) + int8 paged pool:
the bitwise-differential matrix and the no-recompile guard.

Contract (models/scheduler.py module docstring):
``ContinuousScheduler(overlap=True)`` dispatches the device program
for tick N+1 before reading back tick N (non-spec; spec=K overlaps the
deferred retire/admit bookkeeping with its in-poll verify), with every
blocking readback coalesced into ONE ``jax.device_get`` per poll — and
token streams stay BITWISE identical to overlap=False across
{greedy, sampled, spec=K} x {contiguous, paged+prefix-cache}, with
chunked prefill, KV-pressure preemption and the host-RAM tier in the
mix. The int8 PAGED pool (engine kv_dtype=int8 — per-page scale planes
in kv_cache.PagedSlotCache, in-kernel dequant in
kernels/paged_kv.flash_decode_paged) must match the contiguous-int8
reference bitwise, overlap on or off.

The perf contract is guarded structurally: the overlap loop dispatches
the SAME executables as the sync loop (test_overlap_no_new_programs
counts XLA compiles over a mixed refill/preempt/chunked soak), and
stats()["host_ms_per_poll"] reports the host time the pipeline exists
to hide (dispatch-to-dispatch interval minus device wait).
"""

import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from triton_dist_tpu.models import (AutoLLM, ContinuousScheduler, Engine,
                                    Request)
from triton_dist_tpu.models.config import tiny_qwen3

mesh = None
_ENGINES = {}


def setup_module(module):
    global mesh
    n = len(jax.devices())
    mesh = jax.make_mesh((n,), ("tp",))


def _engine(mode, **kw):
    """One model + engine per sampling mode, shared across tests (the
    compiled programs are the expensive part of this file)."""
    key = (mode,) + tuple(sorted(kw.items()))
    if key not in _ENGINES:
        cfg = tiny_qwen3(mesh.shape["tp"])
        model = AutoLLM.from_config(cfg, mesh)
        ekw = dict(sampling="top_k", temperature=0.8) \
            if mode == "sampled" else {}
        ekw.update(kw)
        _ENGINES[key] = (cfg, Engine(model, max_seq=64, backend="xla",
                                     **ekw))
    return _ENGINES[key]


def _mixed_requests(cfg, shared_prefix=None, seed=0):
    """Short and LONG prompts interleaved (5 requests through batch=3
    forces mid-stream refills into recycled slots)."""
    rng = np.random.RandomState(seed)
    spec = [(5, 6), (20, 8), (3, 4), (12, 10), (7, 9)]
    out = []
    for i, (L, g) in enumerate(spec):
        ids = rng.randint(0, cfg.vocab_size, size=(L,)).astype(np.int32)
        if shared_prefix is not None and i % 2:
            ids = np.concatenate([shared_prefix, ids]).astype(np.int32)
        out.append(Request(rid=i, ids=ids, gen_len=g, seed=100 + i))
    return out


def _assert_same_streams(ref, got, tag):
    assert set(ref) == set(got)
    for rid in ref:
        np.testing.assert_array_equal(
            got[rid], ref[rid],
            err_msg=f"{tag}: rid={rid} diverged overlap-on vs off")


# ----------------------------------------------------------------------
# the exactness matrix: {greedy, sampled, spec=K} x {contiguous,
# paged+prefix-cache}, overlap-on vs overlap-off, bitwise — with the
# chunked-prefill mixed tick included in every cell
# ----------------------------------------------------------------------

@pytest.mark.parametrize("paged", [False, True],
                         ids=["contiguous", "paged"])
@pytest.mark.parametrize("mode", ["greedy", "sampled", "spec"])
def test_overlap_matches_sync(mode, paged):
    cfg, eng = _engine(mode)
    pre = None
    skw = {}
    if paged:
        rng = np.random.RandomState(7)
        pre = rng.randint(0, cfg.vocab_size, size=(11,)).astype(np.int32)
        skw = dict(paged=True, page=8)
    if mode == "spec":
        skw["spec"] = 2
    ref = ContinuousScheduler(eng, batch=3, chunk=4, **skw).run(
        _mixed_requests(cfg, pre))
    got = ContinuousScheduler(eng, batch=3, chunk=4, overlap=True,
                              **skw).run(_mixed_requests(cfg, pre))
    _assert_same_streams(ref, got, f"{mode}/{'paged' if paged else 'c'}")
    # chunked prefill: the mixed-tick dispatch/land split
    ref = ContinuousScheduler(eng, batch=3, chunk=4, prefill_budget=3,
                              **skw).run(_mixed_requests(cfg, pre))
    got = ContinuousScheduler(eng, batch=3, chunk=4, prefill_budget=3,
                              overlap=True, **skw).run(
        _mixed_requests(cfg, pre))
    _assert_same_streams(ref, got, f"chunked {mode}")


# ----------------------------------------------------------------------
# preemption + host tier: the drain-before-mutate rule under real
# KV pressure (a preempt/cancel/deadline may never act on a slot whose
# tick is still in flight)
# ----------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["greedy", "spec"])
def test_overlap_preemption_bitwise(mode):
    cfg, eng = _engine(mode)
    page, chunk = 8, 4
    worst = -(-(10 + 8 + chunk - 1) // page)
    tiny = worst + 1 + 1                  # ~1 slot's worst case

    def reqs():
        rng = np.random.RandomState(3)
        return [Request(rid=i,
                        ids=rng.randint(0, cfg.vocab_size,
                                        size=(10,)).astype(np.int32),
                        gen_len=8, seed=100 + i) for i in range(4)]

    skw = dict(paged=True, page=page, num_pages=tiny)
    if mode == "spec":
        skw["spec"] = 2
    ref = ContinuousScheduler(eng, batch=2, chunk=chunk, **skw)
    r1 = ref.run(reqs())
    ovl = ContinuousScheduler(eng, batch=2, chunk=chunk, overlap=True,
                              **skw)
    r2 = ovl.run(reqs())
    _assert_same_streams(r1, r2, f"preempt/{mode}")
    assert ref.preemptions > 0, "pool must actually be under pressure"
    # the drain rule keeps even the preemption SCHEDULE identical: the
    # overlap host mirrors equal the sync mirrors at poll boundaries
    assert ovl.preemptions == ref.preemptions


def test_overlap_host_tier_bitwise():
    cfg, eng = _engine("greedy")
    worst = -(-(10 + 8 + 4 - 1) // 8)
    tiny = worst + 1 + 1

    def reqs():
        rng = np.random.RandomState(5)
        return [Request(rid=i,
                        ids=rng.randint(0, cfg.vocab_size,
                                        size=(10,)).astype(np.int32),
                        gen_len=8) for i in range(4)]

    skw = dict(paged=True, page=8, num_pages=tiny, host_pool_pages=64)
    a = ContinuousScheduler(eng, batch=2, chunk=4, **skw).run(reqs())
    b = ContinuousScheduler(eng, batch=2, chunk=4, overlap=True,
                            **skw).run(reqs())
    _assert_same_streams(a, b, "host-tier")


# ----------------------------------------------------------------------
# int8 paged pool: bitwise vs the contiguous-int8 reference (the
# quantizer is shared — kernels/quant.quantize_kv_int8 — and the paged
# kernel dequants identically), overlap on top
# ----------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["greedy", "spec"])
def test_paged_int8_matches_contiguous_int8(mode):
    cfg, eng8 = _engine(mode, kv_dtype=jnp.int8)
    skw = dict(spec=2) if mode == "spec" else {}

    def reqs():
        rng = np.random.RandomState(11)
        return [Request(rid=i,
                        ids=rng.randint(0, cfg.vocab_size,
                                        size=(12,)).astype(np.int32),
                        gen_len=9, seed=100 + i) for i in range(5)]

    contig = ContinuousScheduler(eng8, batch=3, chunk=4, **skw).run(
        reqs())
    paged = ContinuousScheduler(eng8, batch=3, chunk=4, paged=True,
                                page=8, **skw).run(reqs())
    _assert_same_streams(contig, paged, f"int8/{mode}")
    ovl = ContinuousScheduler(eng8, batch=3, chunk=4, paged=True,
                              page=8, overlap=True, **skw).run(reqs())
    _assert_same_streams(contig, ovl, f"int8 overlap/{mode}")


def test_paged_int8_shares_prefix_pages():
    """Scales ride the page id: prefix sharing + CoW over the int8
    pool must stay bitwise vs cache-off (scales travel with pages
    through the radix tree)."""
    cfg, eng8 = _engine("greedy", kv_dtype=jnp.int8)
    rng = np.random.RandomState(13)
    pre = rng.randint(0, cfg.vocab_size, size=(11,)).astype(np.int32)

    def reqs():
        return _mixed_requests(cfg, pre, seed=2)

    on = ContinuousScheduler(eng8, batch=3, chunk=4, paged=True, page=8,
                             prefix_cache=True)
    got = on.run(reqs())
    off = ContinuousScheduler(eng8, batch=3, chunk=4, paged=True,
                              page=8, prefix_cache=False).run(reqs())
    _assert_same_streams(off, got, "int8 prefix")
    assert on.stats()["hits"] > 0, "prefix cache must actually engage"


# ----------------------------------------------------------------------
# perf structure guards: no new executables, and the gauge exists
# ----------------------------------------------------------------------

class _CompileCounter(logging.Handler):
    def __init__(self):
        super().__init__()
        self.names = []

    def emit(self, record):
        msg = record.getMessage()
        if msg.startswith("Compiling "):
            self.names.append(msg.split()[1])


def test_overlap_no_new_programs():
    """Jit-cache-churn guard: over a mixed refill/preempt/chunked-
    prefill soak, the overlap scheduler must compile ZERO programs the
    sync loop did not already compile — the dispatch/land split reuses
    the same executables with the same shapes (a shape-driven recompile
    would silently hand back the host time the overlap just hid)."""
    cfg, eng = _engine("greedy")
    worst = -(-(31 + 10 + 4 - 1) // 8)
    pool = 2 * worst + 1 + 1

    def soak(overlap):
        sched = ContinuousScheduler(eng, batch=3, chunk=4, paged=True,
                                    page=8, num_pages=pool,
                                    prefill_budget=3, overlap=overlap)
        return sched.run(_mixed_requests(cfg, seed=4)), sched

    counter = _CompileCounter()
    logger = logging.getLogger("jax._src.interpreters.pxla")
    logger.addHandler(counter)
    prev = jax.config.jax_log_compiles
    jax.config.update("jax_log_compiles", True)
    try:
        ref, _ = soak(overlap=False)      # compiles + warms everything
        n_sync = len(counter.names)
        got, sched = soak(overlap=True)
        new = counter.names[n_sync:]
        assert not new, (f"overlap mode compiled {len(new)} program(s) "
                         f"the sync loop never needed: {new}")
    finally:
        jax.config.update("jax_log_compiles", prev)
        logger.removeHandler(counter)
    _assert_same_streams(ref, got, "churn soak")
    assert sched.preemptions >= 0          # soak ran through _admit


def test_overlap_cancel_mid_flight_drains():
    """cancel() while a tick is in flight must drain the pipeline
    first (land + retire), leave the survivor's stream bitwise, and
    conserve the page pool."""
    cfg, eng = _engine("greedy")
    sched = ContinuousScheduler(eng, batch=2, chunk=4, paged=True,
                                page=8, overlap=True)
    reqs = _mixed_requests(cfg)[:2]
    for r in reqs:
        sched.submit(r)
    got = {r.rid: [] for r in reqs}
    for _ in range(50):
        out, _ = sched.poll()
        for rid, t in out.items():
            got[rid].extend(t.tolist())
        if got[0]:
            break
    assert got[0], "rid 0 never streamed"
    sched.cancel(0)                      # mid-flight: forces a drain
    while not sched.idle:
        out, _ = sched.poll()
        for rid, t in out.items():
            got[rid].extend(t.tolist())
    ref = ContinuousScheduler(eng, batch=2, chunk=4, paged=True,
                              page=8).run(_mixed_requests(cfg)[:2])
    np.testing.assert_array_equal(np.asarray(got[1], np.int64), ref[1])
    pool = sched.slots.prefix.pool
    assert pool.available + pool.outstanding == pool.num_pages


def test_overlap_inflight_deadline_drains():
    """A deadline that expires while the rid's tick is in flight must
    route through the drain (land first, then cancel with a visible
    reason) — never mutate an unlanded slot."""
    import time

    cfg, eng = _engine("greedy")
    sched = ContinuousScheduler(eng, batch=1, chunk=4, overlap=True)
    ids = (np.arange(5) % cfg.vocab_size).astype(np.int32)
    sched.submit(Request(rid="a", ids=ids, gen_len=40,
                         deadline_ms=60_000.0))
    sched.poll()                          # admit + dispatch tick 0
    assert not sched._pipeline_idle()
    sched._deadline["a"] = time.monotonic() - 1.0   # force expiry NOW
    done_rids = []
    while not sched.idle:
        _, done = sched.poll()
        done_rids.extend(done)
    assert "a" in done_rids
    assert sched.deadline_expired == 1
    assert "deadline_ms" in sched.rejected.get("a", "")


def test_token_server_overlap_streams_match():
    """The full socket path under overlap=True: concurrent clients get
    the SAME byte streams an overlap=False server produces, and every
    done message carries the host_ms_per_poll gauge (the operator's
    overlap-worth-it signal)."""
    import threading

    from triton_dist_tpu.serving import (ByteTokenizer, TokenServer,
                                         request_stream)

    cfg, eng = _engine("greedy")
    tok = ByteTokenizer(cfg.vocab_size)
    prompts = ["alpha prompt", "second one!", "and a third"]
    N, gen = 3, 16

    def serve(overlap):
        srv = TokenServer(eng, tok, batch=4, chunk=4, paged=True,
                          page=8, overlap=overlap)
        th = threading.Thread(target=srv.serve_forever,
                              kwargs=dict(max_requests=N), daemon=True)
        th.start()
        results, dones = {}, {}

        def client(i):
            toks = []
            for msg in request_stream("127.0.0.1", srv.port,
                                      prompts[i], gen_len=gen):
                if msg.get("done"):
                    dones[i] = msg
                    break
                toks.extend(msg["token_ids"])
            results[i] = toks

        cts = [threading.Thread(target=client, args=(i,))
               for i in range(N)]
        for t in cts:
            t.start()
        for t in cts:
            t.join(timeout=600)
        srv.stop()
        th.join(timeout=60)
        return results, dones

    ref, _ = serve(overlap=False)
    got, dones = serve(overlap=True)
    for i in range(N):
        assert got[i] == ref[i], f"client {i} diverged under overlap"
        assert "host_ms_per_poll" in dones[i]
        assert dones[i]["n_tokens"] == len(got[i])


def test_host_ms_gauge_reports():
    """stats()["host_ms_per_poll"] (and device_wait_s) must be live in
    BOTH modes — the gauge is how an operator decides overlap is worth
    turning on, so it cannot itself depend on the knob."""
    cfg, eng = _engine("greedy")
    for overlap in (False, True):
        sched = ContinuousScheduler(eng, batch=2, chunk=4,
                                    overlap=overlap)
        sched.run(_mixed_requests(cfg)[:3])
        st = sched.stats()
        assert st["overlap"] is overlap
        assert st["host_ms_per_poll"] > 0.0
        assert st["device_wait_s"] > 0.0


# ----------------------------------------------------------------------
# the served path: TokenServer dispatches ahead unless told otherwise
# ----------------------------------------------------------------------

def _decode_dispatches():
    from triton_dist_tpu.runtime.telemetry import default_registry
    return default_registry().snapshot()["engine_decode_dispatches"]


def _served(srv, payloads, hang_up=()):
    """Serve `payloads` (request_stream keyword dicts) through `srv` on
    its own thread until all are done; client i in `hang_up` closes its
    socket after its first message. Returns {i: token ids}."""
    import json
    import socket
    import threading

    from triton_dist_tpu.serving import request_stream

    th = threading.Thread(target=srv.serve_forever,
                          kwargs=dict(max_requests=len(payloads)),
                          daemon=True)
    th.start()
    got = {}

    def client(i):
        toks = []
        if i in hang_up:
            with socket.create_connection(("127.0.0.1", srv.port),
                                          timeout=600) as s:
                f = s.makefile("rw")
                f.write(json.dumps(payloads[i]) + "\n")
                f.flush()
                toks.extend(json.loads(f.readline())["token_ids"])
        else:
            kw = dict(payloads[i])
            for msg in request_stream("127.0.0.1", srv.port,
                                      kw.pop("prompt"), **kw):
                if msg.get("done"):
                    assert "error" not in msg, msg
                    break
                toks.extend(msg["token_ids"])
        got[i] = toks

    cts = [threading.Thread(target=client, args=(i,))
           for i in range(len(payloads))]
    for t in cts:
        t.start()
    for t in cts:
        t.join(timeout=600)
    th.join(timeout=600)
    assert not th.is_alive() and len(got) == len(payloads)
    return got


def test_token_server_dispatches_ahead_by_default():
    """A TokenServer built without `overlap` hands overlap=True to its
    scheduler: every decode tick of a served batch is dispatched ahead
    (`ticks_dispatched_ahead` keeps step with the engine's own count of
    decode dispatches), nothing drains the pipeline until a client
    hangs up mid-stream, and that cancel drains it exactly once. Both
    counters are in stats() and in the /metrics exposition."""
    import socket

    from triton_dist_tpu.serving import ByteTokenizer, TokenServer

    cfg, eng = _engine("greedy")
    tok = ByteTokenizer(cfg.vocab_size)

    def server():
        return TokenServer(eng, tok, batch=4, chunk=4, paged=True,
                           page=8, metrics_port=0)

    srv = server()
    assert srv.sched.overlap is True
    assert srv.stats()["ticks_dispatched_ahead"] == 0
    d0 = _decode_dispatches()
    got = _served(srv, [dict(prompt=p, gen_len=16) for p in
                        ("alpha prompt", "second one!", "and a third")])
    assert all(len(t) == 16 for t in got.values())
    st = srv.stats()
    assert st["ticks_dispatched_ahead"] == _decode_dispatches() - d0 > 0
    assert st["pipeline_drains"] == 0 == st["preemptions"]
    with socket.create_connection(("127.0.0.1", srv.metrics_port),
                                  timeout=30) as s:
        s.sendall(b"GET /metrics HTTP/1.0\r\n\r\n")
        text = b"".join(iter(lambda: s.recv(65536), b"")).decode()
    assert (f"tdtpu_ticks_dispatched_ahead "
            f"{st['ticks_dispatched_ahead']}") in text
    assert "tdtpu_pipeline_drains 0" in text
    srv.stop()

    # a hang-up is seen by the probe of some later iteration; where the
    # stream had finished by then nothing was cancelled: serve again
    for _ in range(5):
        srv = server()
        _served(srv, [dict(prompt="hangs up", gen_len=48),
                      dict(prompt="stays on!", gen_len=48)], hang_up={0})
        st = srv.stats()
        srv.stop()
        if st.get("requests_cancelled"):
            break
    assert st["requests_cancelled"] == 1 == st["pipeline_drains"], st


def test_token_server_grammar_request_matches_sync():
    """A constrained request through the default server collapses the
    pipeline to the synchronous tick for as long as it is live, beside
    an unconstrained stream that goes on dispatching ahead after it:
    both stream what the overlap=False server streams."""
    from triton_dist_tpu.serving import ByteTokenizer, TokenServer

    cfg, eng = _engine("greedy")
    tok = ByteTokenizer(cfg.vocab_size)
    schema = {"type": "object",
              "properties": {"a": {"type": "integer", "maxDigits": 2}}}
    payloads = [dict(prompt="free running", gen_len=40),
                dict(prompt="abcdefgh", gen_len=24,
                     grammar={"type": "json_schema", "schema": schema})]
    runs = {}
    for label, kw in (("sync", dict(overlap=False)), ("default", {})):
        srv = TokenServer(eng, tok, batch=2, chunk=4, paged=True,
                          page=8, **kw)
        d0 = _decode_dispatches()
        runs[label] = _served(srv, payloads)
        st = srv.stats()
        srv.stop()
        assert st["grammar_mask_tokens"] > 0
        ahead = st["ticks_dispatched_ahead"]
        if label == "sync":
            assert ahead == 0 == st["pipeline_drains"]
        else:
            assert 0 < ahead < _decode_dispatches() - d0
    assert runs["default"] == runs["sync"]
