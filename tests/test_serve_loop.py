"""The serve loop's intake (serving.py: acceptor thread -> reader
threads -> inbox -> `TokenServer._intake` on the serve thread), by
counts and order: who calls accept(), when a request parsed during a
poll is admitted, what ends an idle sleep, who answers `busy`, that a
loop with nothing to move does not spin, and how the loop ends. One
tiny engine and one server shape for the whole file; no case reads a
rate."""

import contextlib
import json
import socket
import sys
import threading
import time

import jax
import numpy as np
import pytest

from triton_dist_tpu.models import AutoLLM, Engine
from triton_dist_tpu.models.config import tiny_qwen3
from triton_dist_tpu.serving import (ByteTokenizer, TokenServer,
                                     request_stream)


@pytest.fixture(scope="module")
def tiny():
    mesh = jax.make_mesh((1,), ("tp",))
    cfg = tiny_qwen3(1)
    eng = Engine(AutoLLM.from_config(cfg, mesh), max_seq=64,
                 backend="xla")
    return eng, ByteTokenizer(cfg.vocab_size)


def _server(tiny, **kw):
    eng, tok = tiny
    return TokenServer(eng, tok, batch=2, chunk=4, paged=True, page=8,
                       prefix_cache=False, **kw)


@contextlib.contextmanager
def _serving(srv):
    th = threading.Thread(target=srv.serve_forever, daemon=True)
    th.start()
    try:
        yield th
    finally:
        srv.stop()
        th.join(timeout=120)
        assert not th.is_alive()


def _spy_phases(srv):
    """Every phase the server opens from here on, as (name, phase):
    `phase._t0` and `phase.dt` are readable once it has exited."""
    opened = []
    tele = srv.sched.tele
    phase = tele.phase

    def spy(name):
        ph = phase(name)
        opened.append((name, ph))
        return ph
    tele.phase = spy
    return opened


def _want(tiny, prompt, gen_len):
    eng, tok = tiny
    ids = np.asarray(tok.encode(prompt), np.int32)
    return [int(t) for t in
            np.asarray(eng.serve(np.tile(ids[None], (2, 1)), gen_len))[0]]


def _stream(srv, prompt, gen_len, **kw):
    return [t for msg in request_stream("127.0.0.1", srv.port, prompt,
                                        gen_len=gen_len, **kw)
            for t in msg.get("token_ids", [])]


def _raw(srv, prompt, gen_len):
    s = socket.create_connection(("127.0.0.1", srv.port), timeout=300)
    f = s.makefile("rw")
    f.write(json.dumps({"prompt": prompt, "gen_len": gen_len}) + "\n")
    f.flush()
    return s, f


def _until(cond, what, timeout=300.0):
    t0 = time.monotonic()
    while not cond():
        assert time.monotonic() - t0 < timeout, f"never: {what}"
        time.sleep(0.002)


def _stamps(srv, rid):
    """{event: monotonic seconds of its first stamp} of one traced
    request."""
    tele = srv.sched.tele
    at = {}
    for ms, name, _ in tele.export()["requests"][str(rid)]["events"]:
        at.setdefault(name, tele._t0 + ms / 1e3)
    return at


class _HeldPoll:
    """`sched.poll` behind a gate: once armed, the next poll stands
    still (the serve lock held, as a poll waiting for the device holds
    it) until released. `spans` has (start, end) of every poll."""

    def __init__(self, srv):
        self.spans = []
        self._armed = threading.Event()
        self._entered = threading.Event()
        self._release = threading.Event()
        inner = srv.sched.poll

        def poll():
            t0 = time.monotonic()
            if self._armed.is_set() and not self._entered.is_set():
                self._entered.set()
                assert self._release.wait(300)
            res = inner()
            self.spans.append((t0, time.monotonic()))
            return res
        srv.sched.poll = poll

    def hold(self) -> int:
        """Returns the index in `spans` that the held poll will get."""
        self._armed.set()
        assert self._entered.wait(120)
        return len(self.spans)

    def release(self):
        self._release.set()


class _CountingListener:
    """The listening socket with the thread of every accept() call
    noted (a socket takes no new attribute, hence the proxy)."""

    def __init__(self, sock, callers):
        self._sock = sock
        self._callers = callers

    def accept(self):
        self._callers.append(threading.current_thread().name)
        return self._sock.accept()

    def __getattr__(self, name):
        return getattr(self._sock, name)


def test_streams_live_the_loop_waits_for_no_connection(tiny):
    """Three streams through two slots: accept() is only ever called by
    the acceptor thread (which picks up a listener wrapped on the
    RUNNING server), the serve thread's `accept_wait` phases have exits
    and seconds, and they are intakes, not waits: the old loop sat out
    20 ms in every one of them."""
    work = [("alpha prompt", 24), ("second one!", 20), ("third", 16)]
    srv = _server(tiny)
    opened = _spy_phases(srv)
    callers, got = [], {}
    with _serving(srv) as th:
        srv._sock = _CountingListener(srv._sock, callers)
        time.sleep(0.3)         # the acceptor's pass in the raw accept()
        cts = [threading.Thread(
            target=lambda i=i: got.__setitem__(i, _stream(srv, *work[i])))
            for i in range(len(work))]
        for t in cts:
            t.start()
        for t in cts:
            t.join(timeout=600)
        serve_thread = th.name
    for i, (prompt, gen_len) in enumerate(work):
        assert got[i] == _want(tiny, prompt, gen_len), i
    assert callers and set(callers) == {"serve-acceptor"} \
        and serve_thread not in callers
    st = srv.stats()
    intakes = sorted(ph.dt for name, ph in opened if name == "accept_wait")
    assert len(intakes) == st["host_phase_n{phase=accept_wait}"] \
        == st["host_phase_n{phase=loop}"] > 10
    assert st["host_phase_s"]["accept_wait"] > 0
    # not one in ten of them reaches 15 ms (a descheduled thread may)
    assert intakes[(9 * len(intakes)) // 10] < 0.015, intakes[-5:]
    assert sum(intakes) < 0.05 * sum(
        ph.dt for name, ph in opened if name == "loop")


def test_request_parsed_during_a_poll_is_admitted_by_the_next(tiny):
    """A poll stands still (stubbed) with the serve lock held; a client
    connects meanwhile. Its `accepted` stamp and its place in the inbox
    exist before that poll returns, the poll that was in flight does
    not admit it, the next one does."""
    srv = _server(tiny, trace=True)
    gate = _HeldPoll(srv)
    spans, got = gate.spans, {}
    with _serving(srv):
        held = gate.hold()
        client = threading.Thread(
            target=lambda: got.setdefault(0, _stream(srv, "mid-poll", 8)))
        client.start()
        _until(lambda: len(srv._inbox) == 1, "the request in the inbox")
        accepted_at = srv._inbox[0].req.accepted_at
        assert accepted_at is not None and len(spans) == held
        assert srv.sched.queue_depth == 0 and not srv._conns
        gate.release()
        client.join(timeout=600)
    assert got[0] == _want(tiny, "mid-poll", 8)
    at = _stamps(srv, 0)
    t_held, t_next = spans[held], spans[held + 1]
    eps = 1e-5                  # the lifecycle's stamps are rounded
    assert at["accepted"] == pytest.approx(accepted_at, abs=1e-3)
    assert at["accepted"] < t_held[1]
    assert t_held[1] - eps <= at["queued"] <= t_next[0] + eps
    assert t_next[0] - eps <= at["admitted"] <= t_next[1] + eps


def test_request_to_an_idle_server_ends_the_sleep(tiny):
    """Requests one after another to a server with nothing in flight:
    at most one idle sleep ends between a request's `accepted` and its
    `admitted` (the one it cut short), and `serve_idle_wakeups` counts
    such sleeps, of `host_phase_n{phase=idle_sleep}` in all."""
    srv = _server(tiny, trace=True)
    opened = _spy_phases(srv)
    with _serving(srv):
        _until(lambda: srv.stats()["host_phase_n{phase=idle_sleep}"] >= 3,
               "three idle sleeps")
        assert srv.stats()["serve_idle_wakeups"] == 0
        for i in range(3):
            assert len(_stream(srv, f"{i} to an idle server", 4)) == 4
            time.sleep(0.12)
        st = srv.stats()
    sleeps = [ph._t0 + ph.dt for name, ph in opened
              if name == "idle_sleep" and hasattr(ph, "dt")]
    for rid in range(3):
        at = _stamps(srv, rid)
        between = [t for t in sleeps if at["accepted"] < t < at["admitted"]]
        assert len(between) <= 1, (rid, between, at)
    assert 1 <= st["serve_idle_wakeups"] <= 3
    assert st["serve_idle_wakeups"] < st["host_phase_n{phase=idle_sleep}"]


def test_overflow_is_answered_busy_through_the_inbox(tiny):
    """max_queue=1 behind two live streams: two requests parsed during
    one poll are taken in order, the first into the line, the second
    answered {"busy", "retry_after_ms"} (by its reader: the loop only
    says no) with nothing of it registered; the streams that were let
    in arrive whole."""
    srv = _server(tiny, max_queue=1)
    gate = _HeldPoll(srv)
    long_ = [("first occupant", 40), ("2nd occupant", 40)]
    with _serving(srv):
        # one occupant at a time: two parsed before the loop's first
        # intake (a server thread slow to start, on a loaded machine)
        # would meet max_queue=1 themselves
        held, first = [], []
        for p, g in long_:
            held.append(_raw(srv, p, g))
            first.append(json.loads(held[-1][1].readline()))
        assert all(m.get("token_ids") for m in first), first
        gate.hold()
        sq, fq = _raw(srv, "queued behind them", 6)
        _until(lambda: len(srv._inbox) == 1, "the first in the inbox")
        sb, fb = _raw(srv, "one too many", 6)
        _until(lambda: len(srv._inbox) == 2, "the second in the inbox")
        gate.release()
        busy = json.loads(fb.readline())
        assert busy.get("busy") is True and busy["retry_after_ms"] >= 25
        assert fb.readline() == ""              # refused, then closed
        assert srv.stats()["busy_rejections"] == 1
        assert srv._next_rid == 4 and 3 not in srv._conns
        for (s, f), m0, (prompt, gen_len) in zip(held, first, long_):
            msgs = [m0] + [json.loads(line) for line in f]
            assert msgs[-1].get("done") and not msgs[-1].get("error")
            toks = [t for m in msgs for t in m.get("token_ids", [])]
            assert toks == _want(tiny, prompt, gen_len), prompt
            s.close()
        msgs = [json.loads(line) for line in fq]
        assert [t for m in msgs for t in m.get("token_ids", [])] \
            == _want(tiny, "queued behind them", 6)
        for s in (sq, sb):
            s.close()


def test_concurrent_clients_lose_no_first_token(tiny):
    """Six clients at once (all parsed during one poll) on two slots
    and a line of one: one is let in and five are refused at the first
    intake, whoever is refused comes back (request_stream's busy
    retries), and every stream is whole from its first token on: its
    `_conns` entry was there before the poll that emitted for it.
    Threads switch every 10 us meanwhile: every try got a rid of its
    own and a verdict, none was lost between the threads."""
    work = [(f"{chr(97 + i)} concurrent client", 6 + i) for i in range(6)]
    srv = _server(tiny, max_queue=1)
    gate = _HeldPoll(srv)
    got = {}
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        with _serving(srv):
            gate.hold()
            cts = [threading.Thread(target=lambda i=i: got.__setitem__(
                i, _stream(srv, *work[i], busy_retries=400)))
                for i in range(len(work))]
            for t in cts:
                t.start()
            _until(lambda: len(srv._inbox) == len(work),
                   "six in the inbox")
            gate.release()
            for t in cts:
                t.join(timeout=600)
                assert not t.is_alive()
            st = srv.stats()
    finally:
        sys.setswitchinterval(interval)
    for i, (prompt, gen_len) in enumerate(work):
        assert got.get(i) == _want(tiny, prompt, gen_len), i
    assert st["busy_rejections"] >= 5
    assert st["requests_retired"] == len(work)
    assert srv._next_rid == len(work) + st["busy_rejections"]
    assert not srv._inbox and not srv._conns


class _Stalled:
    """A scheduler that is never idle and whose poll moves nothing and
    waits for nothing: a disaggregated prefill still out, a queued
    request that cannot be admitted yet."""

    idle = False

    def __init__(self, sched):
        self._sched = sched

    def poll(self):
        return {}, []

    def __getattr__(self, name):
        return getattr(self._sched, name)


def test_loop_that_moves_nothing_does_not_spin(tiny):
    """... it sleeps on the wake event, bounded by the 20 ms that
    paced the old loop: at most 100 iterations in a second (a spin
    would count tens of thousands), and still some."""
    srv = _server(tiny)
    srv.sched = _Stalled(srv.sched)
    with _serving(srv):
        _until(lambda: srv.stats()["serve_loop_iterations"] >= 2,
               "the loop running")
        n0 = srv.stats()["serve_loop_iterations"]
        time.sleep(1.0)
        n = srv.stats()["serve_loop_iterations"] - n0
    assert 5 <= n <= 100, n
    st = srv.stats()
    assert st["host_phase_n{phase=idle_sleep}"] >= n
    assert st["serve_idle_wakeups"] == 0


def test_stop_ends_the_acceptor_and_refuses_connections(tiny):
    """stop(): the loop's teardown closes the listener, the acceptor
    thread ends with it, a new connection is refused at once, and a
    request whose reader comes too late is told so, not left hanging."""
    srv = _server(tiny)
    with _serving(srv):
        assert len(_stream(srv, "before the stop", 4)) == 4
        acceptor = srv._accept_thread
        assert acceptor.is_alive() and acceptor.name == "serve-acceptor"
    assert not acceptor.is_alive()
    with pytest.raises(OSError):
        socket.create_connection(("127.0.0.1", srv.port), timeout=5)
    ours, theirs = socket.socketpair()
    with ours, ours.makefile("rw") as f:
        f.write(json.dumps({"prompt": "too late", "gen_len": 4}) + "\n")
        f.flush()
        srv._reader(theirs, time.monotonic())
        msg = json.loads(f.readline())
    assert msg["done"] and msg["n_tokens"] == 0 \
        and "stopped" in msg["error"]
    assert not srv._inbox and not srv._conns
