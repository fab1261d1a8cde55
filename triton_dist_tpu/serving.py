"""Socket serving: a continuously-batched streaming token server +
client over the Engine.

TPU re-design of the reference's serving pair
(`mega_triton_kernel/test/models/model_server.py:265` — a TCP server
that tokenizes prompts, prefills, and streams sampled tokens — and the
interactive `chat.py:207` client). Protocol is line-delimited JSON over
TCP:

  client -> {"prompt": str, "gen_len": int, "seed": int}\n
  server -> {"text": str, "token_ids": [...]}\n        per decode chunk
            {"done": true, "n_tokens": int}\n          terminator

Tokens stream INCREMENTALLY: the decode runs in chunks of `chunk`
steps (each chunk one jitted scan), so clients render text while the
model is still generating. The server is MULTI-CLIENT (continuous
batching, models/scheduler.py): up to `batch` concurrent requests
decode in distinct slots of one slot scan — distinct prompts, per-slot
positions and PRNG chains — and a finished client's slot is refilled
from the accept queue between chunks while the other streams keep
flowing. Chunked decode is token-exact vs Engine.serve() in BOTH
sampling modes (greedy: same argmax chain; sampled: the scan's evolved
key chains across chunks).

paged=True additionally serves over the paged KV pool with the
SHARED-PREFIX radix cache (models/prefix_cache.py): prompts sharing a
system-prompt/few-shot prefix reuse its cached KV pages and skip that
prefill work — token streams stay bitwise identical to prefix_cache=
False. The final {"done": ...} message then reports a "cache" dict
(hit rate, prefill tokens skipped). Clients that hang up mid-stream
are detected (EOF probe or failed write) and their slot is CANCELLED —
pages freed and the partial sequence inserted into the prefix tree —
instead of decoding to gen_len for nobody.

Resilience (models/scheduler.py has the scheduler-side story):
- a malformed request (bad JSON, over-capacity prompt, an unbounded
  garbage "line" past _MAX_LINE bytes) gets a structured
  {"done": true, "error": ...} refusal before the close — never a
  silent slam, never a ballooning reader buffer;
- max_queue bounds the accept line: overflow is answered with
  {"busy": true, "retry_after_ms": ...} (retry_after scaled by the
  measured poll cadence x queue depth), and request_stream retries it
  with bounded backoff — as it retries refused connects during server
  startup;
- requests may carry "deadline_ms"; an expired request is cancelled
  with a visible error in its done message;
- under KV-pool pressure the scheduler PREEMPTS a victim slot instead
  of rejecting (the client just sees a pause — resumed streams are
  bitwise identical), and a hung decode chunk (watchdog_s) ends the
  loop with a HANG error to every live client instead of freezing.

Multi-chip TP: build the model over a TP mesh and ONE TokenServer
drives every chip — the paged pool is head-sharded and the slot scan
runs under shard_map with the projections on the TP comm backends
(models/kv_cache.py TP SHARDING + models/scheduler.py module
docstring); streams are bitwise identical TP=N vs TP=1 and stats()
reports tp_size plus aggregate AND per-chip tok/s
(tests/test_tp_serving.py).

Telemetry (runtime/telemetry.py): stats() is a deep registry snapshot
with live `ttft_ms` / `inter_token_ms` p50/p95/p99 histograms; any
client can fetch it in-protocol with a `{"op": "stats"}` request
(one JSON reply line, then close). `metrics_port=` starts a minimal
Prometheus text-exposition listener (`GET /metrics` over HTTP/1.0 —
scrape `http://host:server.metrics_port/metrics`), and
`TDTPU_TRACE=path` enables poll-loop tracing AND dumps the
perfetto-loadable timeline + request traces to `path` when
serve_forever exits (summarize with tools/trace_view.py). Tracing or
not, every iteration of serve_forever is a `serve:loop` phase over
`serve:accept_wait` / `poll` / `wire_write` / `probe` / `idle_sleep`
(Telemetry.phase): self-time totals in stats()["host_phase_s"], and
annotations that a jax.profiler session attached to the live server
shows beside the device's operations. `accept_wait` is the loop's
INTAKE: it waits for nothing there. An acceptor thread owns the
listening socket's accept(), a reader thread per connection parses the
request line and puts it into the loop's inbox, and the loop takes in
what arrived since its last iteration (rid, `_conns` entry, submit)
before it polls; neither thread opens a phase, so the phases still
partition the serve thread's wall time. A loop with nothing to do
sleeps on the inbox's wake event (`serve_idle_wakeups` counts the
sleeps a request cut short), never on a timer with a chunk in flight.
"""

from __future__ import annotations

import collections
import json
import os
import random
import socket
import threading
import time
from typing import Iterator, Optional

import numpy as np

# longest accepted request line: a protocol message is a few hundred
# bytes beside its prompt; anything bigger is a firehose and gets a
# structured refusal. A server whose slots hold long contexts takes the
# prompts that fill them: its cap is the larger of this and 16 bytes a
# position of the engine's max_seq (a token id in decimal with its
# separator is at most 7)
_MAX_LINE = 65536


class ServerBusy(RuntimeError):
    """request_stream exhausted its busy retries; retry_after_ms is the
    server's latest hint."""

    def __init__(self, retry_after_ms: float):
        super().__init__(
            f"server busy (retry_after_ms={retry_after_ms:g})")
        self.retry_after_ms = retry_after_ms


class ByteTokenizer:
    """Toy byte-level tokenizer capped to a vocab (examples/07's demo
    tokenizer, importable for the serving tests)."""

    def __init__(self, vocab_size: int):
        self.vocab_size = vocab_size

    def encode(self, text: str):
        return [b % self.vocab_size for b in text.encode()]

    def decode(self, ids):
        return bytes(int(i) % 256 for i in ids).decode("latin-1")


def decode_stream(engine, logits, cache, gen_len: int, *, chunk: int = 4,
                  seed: int = 0):
    """Yield token chunks [B, <=chunk] as they are generated: each chunk
    is one jitted decode scan, with (logits, cache) carried between
    chunks (the cache is donated into each scan, so memory stays flat).
    Chunking is exact in BOTH modes: greedy because the argmax chain is
    identical to one gen_len-long scan, and sampled because the scan
    returns its evolved PRNG key and the next chunk resumes the chain —
    the sampled stream equals Engine.serve() at the same seed for every
    chunk size (it used to re-split a fresh key per chunk and diverge)."""
    import jax
    key = jax.random.key(seed)
    done = 0
    while done < gen_len:
        g = min(chunk, gen_len - done)
        if engine.sampling == "greedy":
            toks, logits, cache = engine._decode_scan(
                engine.model, logits, cache, gen_len=g)
        else:
            toks, logits, cache, key = engine._decode_scan(
                engine.model, logits, cache, key, gen_len=g)
        yield np.asarray(toks)
        done += g


class TokenServer:
    """Accept prompts, stream decode chunks back (reference:
    model_server.py's request loop), now CONTINUOUSLY BATCHED: up to
    `batch` clients decode concurrently, each in its own slot of the
    scheduler (models/scheduler.py) — distinct requests, distinct KV
    rows, one jitted slot scan per chunk. A freed slot is refilled
    from the connection queue between chunks while the other clients'
    streams keep flowing. Still single-threaded ON THE MODEL: socket
    threads only accept, parse requests and write refusals; every jax
    dispatch, every rid and every `_conns` entry is the serve_forever
    thread's (concurrency is batching, not model threads — the
    discipline the old one-request loop had, kept)."""

    def __init__(self, engine, tokenizer, *, batch: int,
                 host: str = "127.0.0.1", port: int = 0,
                 chunk: int = 4, paged: bool = False,
                 prefix_cache: bool = True, page: int = 16,
                 num_pages: Optional[int] = None, spec: int = 0,
                 drafter=None, max_queue: Optional[int] = None,
                 watchdog_s: Optional[float] = None, fault=None,
                 prefill_budget: Optional[int] = None,
                 host_pool_pages: int = 0, overlap: bool = True,
                 metrics_port: Optional[int] = None,
                 trace: Optional[bool] = None,
                 disagg: bool = False, prefill_workers: int = 1,
                 disagg_threads: bool = True, transport=None,
                 slo_classes: Optional[dict] = None,
                 max_forks: int = 8,
                 replica_id: Optional[str] = None):
        """paged=True serves over the paged KV pool with the
        shared-prefix radix cache (models/prefix_cache.py): concurrent
        prompts sharing a system-prompt/few-shot prefix reuse its
        cached KV pages and skip that prefill; the final {"done": ...}
        message then carries a "cache" dict (hit rate, prefill tokens
        skipped) and stats() exposes the running counters.

        spec=K > 0 turns each decode step into a speculative
        draft-then-verify iteration (models/spec_decode.py, n-gram
        prompt-lookup drafting by default): every slot streams 1..K+1
        tokens per model forward, token-for-token identical to spec=0
        under greedy sampling. stats() then also reports
        spec_accept_rate and tokens_per_step.

        max_queue bounds the waiting line (overflow clients get
        {"busy": true, "retry_after_ms": ...}); watchdog_s deadlines
        every decode chunk (a hang ends serve_forever with a clean
        error to every client); fault is a chaos hook
        (runtime/chaos.py::FaultInjector) for resilience tests.

        prefill_budget enables CHUNKED PREFILL (Sarathi-Serve — the
        models/scheduler.py docstring has the design): a long prompt's
        admission no longer stalls every live client's stream for its
        whole prefill; at most `prefill_budget` prompt tokens ride
        each decode step until the prompt is absorbed and its slot
        starts streaming. Token streams are bitwise identical either
        way — this knob trades a bounded per-step latency bump for the
        removal of multi-hundred-ms inter-token spikes under load.

        host_pool_pages enables the HOST-RAM KV TIER on the paged path
        (models/kv_tier.py): evicted prefix spans demote to a host
        pool of that many device-page-sized buffers instead of being
        dropped, and a returning tenant's prefix promotes back into
        fresh device pages — the effective cache becomes
        num_pages + host_pool_pages. stats() (and each done message's
        "cache" dict) then reports host_hits / host_pages_resident /
        demotions / promotions / restore_latency_ms live.

        overlap: the server DISPATCHES AHEAD (models/scheduler.py
        module docstring): the driver dispatches the next device tick
        before it reads back the previous one, so this server's host
        work a poll — admissions, drafting, the socket writes, the
        disconnect probes and the inbox's intake — runs while the
        device computes instead of serializing with it. Token streams
        are bitwise those of the synchronous loop; the watchdog and
        deadline checks sit at landed-tick boundaries (a dispatch
        cannot hang — the readback can); a first token leaves one poll
        later and a freed slot re-admits one tick later.
        overlap=False is the CONTROL, the synchronous loop the bitwise
        tests compare against, not a tuning choice. stats() says how
        often the mechanism engages (`ticks_dispatched_ahead` over the
        engine's `engine_decode_dispatches`), how often the pipeline
        collapsed (`pipeline_drains`: a preemption, a cancel, an
        in-flight deadline, a PoolExhausted admission, a grammar
        tick), and the host time it hides (`host_ms_per_poll`, also in
        every done message).

        metrics_port: not None starts a Prometheus text-exposition
        listener on that TCP port (0 = ephemeral; the bound port is
        `self.metrics_port`) — `GET /metrics` returns the scheduler's
        registry plus the process-global one (Engine dispatch
        counters) in exposition format v0.0.4.

        trace: poll-loop + request tracing (runtime/telemetry.py,
        perfetto-loadable; None = the TDTPU_TRACE env convention —
        setting TDTPU_TRACE=path also makes serve_forever dump the
        trace to `path` on exit). Clients can fetch the live stats
        snapshot — ttft_ms / inter_token_ms histograms included —
        with a `{"op": "stats"}` request.

        disagg=True serves in PREFILL/DECODE DISAGGREGATED mode
        (models/disagg.py — the DistServe split): admissions prefill
        on `prefill_workers` dedicated workers (their own threads by
        default — disagg_threads) and stream finished KV pages to the
        decode mesh over `transport` (HostTransport default;
        ICITransport/DCNTransport for the device tiers), so decode
        polls never carry a prefill q_len and inter-token latency
        stays flat under long-prompt admission load. Always paged;
        mutually exclusive with prefill_budget (chunked prefill is
        the fused alternative disaggregation replaces). Streams are
        bitwise identical either way (tests/test_disagg.py).

        slo_classes: the SLO classes clients may tag requests with
        (the in-protocol `"slo"` field — e.g. "interactive"/"batch";
        None = runtime/telemetry.DEFAULT_SLO_CLASSES). Tagged
        requests land their lifecycle latencies in per-class
        `ttft_ms{slo=...}` / `inter_token_ms{slo=...}` histograms and
        partition into `slo_goodput`/`slo_violations` counters —
        visible in stats(), `{"op": "stats"}` and `/metrics`. An
        unknown class tag on a request is REFUSED (bounded metric
        cardinality) with the configured names in the error.

        max_forks caps the in-protocol `"n"` field (parallel sampling:
        one prefill, n KV-forked decode slots — models/structured.py
        has the subsystem story). A request may also carry a
        `"grammar"` spec ({"type": "json_schema", "schema": ...} or
        {"type": "token_fsm", ...}) compiled server-side against the
        byte vocab; n<=0, n over the cap, n>1 without paged=True, and
        a malformed grammar all get the structured {"done", error}
        refusal with the parse error echoed — never a crashed poll
        loop. Fork chunks are tagged {"fork": k} and the n streams
        share ONE fan-in done message once every fork finishes.

        replica_id names this server inside a FLEET (fleet/router.py):
        when set, every done message and stats() snapshot carries
        ``"replica"`` — the retire event a router's shadow placement
        index consumes — and `{"op": "stats"}` doubles as the identity
        handshake of a membership health probe. Requests may also tag a
        ``"session"`` field (any string up to 128 chars): the server
        accepts and ignores it, the ROUTER uses it for session
        affinity, so one client codepath speaks to both a bare server
        and a fleet. A ``"request_id"`` field (non-empty string up to
        128 chars) rides the same contract: validated and ignored
        here, it is the idempotency key the HA router tier
        (fleet/ha.py) dedups on for exactly-once delivery."""
        from triton_dist_tpu.models.disagg import DisaggScheduler
        from triton_dist_tpu.models.scheduler import ContinuousScheduler
        self.engine = engine
        self._max_line = max(_MAX_LINE,
                             16 * int(getattr(engine, "max_seq", 0)))
        self.tok = tokenizer
        self.batch = batch
        self.chunk = chunk
        self.paged = paged or disagg
        if disagg:
            if prefill_budget is not None:
                raise ValueError(
                    "disagg=True replaces chunked prefill — drop "
                    "prefill_budget (the decode mesh never prefills)")
            self.sched = DisaggScheduler(
                engine, batch=batch, chunk=chunk,
                prefix_cache=prefix_cache, page=page,
                num_pages=num_pages, spec=spec, drafter=drafter,
                max_queue=max_queue, watchdog_s=watchdog_s,
                fault=fault, host_pool_pages=host_pool_pages,
                overlap=overlap, trace=trace,
                prefill_workers=prefill_workers,
                threads=disagg_threads, transport=transport,
                slo_classes=slo_classes)
        else:
            self.sched = ContinuousScheduler(
                engine, batch=batch, chunk=chunk, paged=paged,
                prefix_cache=prefix_cache, page=page,
                num_pages=num_pages, spec=spec, drafter=drafter,
                max_queue=max_queue, watchdog_s=watchdog_s,
                fault=fault, prefill_budget=prefill_budget,
                host_pool_pages=host_pool_pages, overlap=overlap,
                trace=trace, slo_classes=slo_classes)
        self.max_forks = max_forks
        self.replica_id = replica_id
        self._vocab = None       # lazy byte vocab for grammar compiles
        self._poll_ema = 0.05    # measured poll cadence, seeds retry_after
        self._sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._sock.bind((host, port))
        self._sock.listen(max(4, batch))
        self.host, self.port = self._sock.getsockname()
        self._stop = threading.Event()
        # iterations of serve_forever's loop: over them, the loop's
        # time outside its intake (host_phase_s of every phase but
        # accept_wait and idle_sleep) is the mean gap between two
        # intakes, which a parsed request sits out in the inbox
        reg = self.sched.tele.registry
        self._c_iterations = reg.counter(
            "serve_loop_iterations", "iterations of the serve loop")
        self._c_idle_wakeups = reg.counter(
            "serve_idle_wakeups",
            "sleeps of the serve loop that a request in its inbox cut "
            "short (of host_phase_n{phase=idle_sleep} sleeps in all)")
        # reader threads -> serve loop: parsed requests in arrival
        # order. _wake is set by every put and by stop(); _inbox_lock
        # only orders a put against the loop's teardown (_closed), the
        # loop itself pops without it
        self._inbox: collections.deque = collections.deque()
        self._inbox_lock = threading.Lock()
        self._closed = False
        self._wake = threading.Event()
        self._accept_thread: Optional[threading.Thread] = None
        self._next_rid = 0              # serve thread only
        self._conns: dict = {}          # rid -> _ClientStream, likewise
        # guards poll() against cross-thread stats() and cancel
        self._lock = threading.Lock()
        # optional Prometheus /metrics listener (daemon thread; dies
        # with stop()). metrics_port=0 binds an ephemeral port.
        self.metrics_port: Optional[int] = None
        self._msock: Optional[socket.socket] = None
        if metrics_port is not None:
            self._msock = socket.socket(socket.AF_INET,
                                        socket.SOCK_STREAM)
            self._msock.setsockopt(socket.SOL_SOCKET,
                                   socket.SO_REUSEADDR, 1)
            self._msock.bind((host, metrics_port))
            self._msock.listen(4)
            self._msock.settimeout(0.25)
            self.metrics_port = self._msock.getsockname()[1]
            threading.Thread(target=self._serve_metrics,
                             daemon=True).start()

    class _Arrival:
        """A parsed request on its way from its reader thread to the
        serve loop, and the loop's verdict on its way back: `accepted`
        is submit()'s answer (None where the loop ended first), `hint`
        the retry_after_ms of a refusal."""

        __slots__ = ("req", "conn", "fh", "accepted", "hint", "ready")

        def __init__(self, req, conn, fh):
            self.req = req
            self.conn = conn
            self.fh = fh
            self.accepted: Optional[bool] = None
            self.hint = 0
            self.ready = threading.Event()

    class _ClientStream:
        """Per-connection state: the socket + reply file handle + token
        count. Owned by the model loop from the intake on; the reader
        thread only hands it over."""

        def __init__(self, conn, fh):
            self.conn = conn
            self.fh = fh
            self.n = 0
            self.dead = False
            self.n_left = 1     # forks still streaming (fan-in count)
            self.errors = []    # per-fork failure reasons, fan-in done

    @staticmethod
    def _refuse(conn, f, msg: dict) -> None:
        """Best-effort structured refusal, then close: a bad or
        refused request gets a visible reason, never a silent slam.
        Before closing, signal end-of-stream and BRIEFLY drain unread
        input (the oversized-line path leaves the rest of the firehose
        in the receive queue; closing with unread bytes makes TCP send
        RST, which can discard the refusal before the client reads it).
        The drain is bounded in time and bytes so an endless firehose
        cannot park this thread."""
        try:
            f.write(json.dumps(msg) + "\n")
            f.flush()
        except (OSError, ValueError):
            pass
        try:
            conn.shutdown(socket.SHUT_WR)
            conn.settimeout(0.25)
            drained, t0 = 0, time.monotonic()
            while drained < (4 << 20) and time.monotonic() - t0 < 1.0:
                chunk = conn.recv(65536)
                if not chunk:
                    break
                drained += len(chunk)
        except OSError:
            pass
        for closer in (f.close, conn.close):
            try:
                closer()
            except OSError:
                pass

    def _reader(self, conn: socket.socket,
                accepted_at: Optional[float] = None) -> None:
        """Connection thread: parse ONE request line (capped at
        `_max_line` bytes — a garbage firehose cannot balloon this
        thread), put it into the model loop's inbox, wait for the
        loop's verdict, leave the socket open for streaming replies.
        Every refusal — malformed JSON, over-capacity prompt, oversized
        line, full queue — is answered with a structured line before
        the close, and from HERE (_refuse may block for a second; the
        loop only says yes or no). accepted_at: the monotonic stamp of
        accept()'s return, the first event of the request's traced
        lifecycle."""
        import sys
        from triton_dist_tpu.models.scheduler import Request
        try:
            conn.settimeout(60.0)   # a silent client cannot hold a slot
            f = conn.makefile("rw")
            try:
                line = f.readline(self._max_line + 1)
            except UnicodeDecodeError:
                # the reply side of the text-mode file is independent
                # of the poisoned read side — refuse, don't hang the
                # client until its timeout
                self._refuse(conn, f, {
                    "done": True, "n_tokens": 0,
                    "error": "bad request: line is not valid UTF-8"})
                return
            if not line.strip():
                conn.close()
                return
            # readline's cap counts decoded CHARACTERS; the contract is
            # BYTES (multi-byte UTF-8 would otherwise stretch it 4x)
            if len(line) > self._max_line \
                    or len(line.encode()) > self._max_line:
                self._refuse(conn, f, {
                    "done": True, "n_tokens": 0,
                    "error": f"request line exceeds {self._max_line} "
                             f"bytes"})
                return
            try:
                req = json.loads(line)
                if not isinstance(req, dict):
                    raise ValueError("request must be a JSON object")
                if req.get("op") == "stats":
                    # in-protocol stats fetch: one deep-snapshot JSON
                    # reply (live ttft/inter-token histograms
                    # included), then close — no slot consumed
                    self._refuse(conn, f, {"done": True,
                                           "stats": self.stats()})
                    return
                ids = self.tok.encode(str(req.get("prompt", ""))) or [0]
                gen_len = int(req.get("gen_len", 16))
                seed = int(req.get("seed", 0))
                n = int(req.get("n", 1))
                if n < 1:
                    raise ValueError(f"bad n={n}: must be >= 1")
                if n > self.max_forks:
                    raise ValueError(
                        f"n={n} exceeds max_forks cap {self.max_forks}")
                if n > 1 and not self.paged:
                    raise ValueError(
                        "n>1 parallel sampling needs paged=True (the "
                        "KV fork shares the prompt's pages)")
                if n > 1:
                    self.engine.refuse_slot_state(
                        f"n={n}", "KV fork: a fork shares pages, and "
                                  "state is not shared")
                grammar = req.get("grammar")
                gspec = None
                if grammar is not None:
                    # compiled HERE so a malformed spec refuses at the
                    # wire with the parse error echoed, never inside
                    # the poll loop
                    from triton_dist_tpu.models.structured import \
                        GrammarSpec
                    if not isinstance(grammar, dict):
                        raise ValueError(
                            "grammar must be a JSON object")
                    gspec = GrammarSpec.from_wire(
                        grammar, self._byte_vocab())
                deadline_ms = req.get("deadline_ms")
                if deadline_ms is not None:
                    deadline_ms = float(deadline_ms)
                session = req.get("session")
                if session is not None:
                    # accepted (and bounded) so one client codepath
                    # works against a bare server and a fleet router;
                    # affinity itself is ROUTER state (fleet/router.py)
                    if not isinstance(session, str) or \
                            len(session) > 128:
                        raise ValueError(
                            "session must be a string of <= 128 chars")
                request_id = req.get("request_id")
                if request_id is not None:
                    # same contract as session: validated + ignored by
                    # a bare server; the exactly-once dedup window is
                    # ROUTER state (fleet/ha.py journal watermarks)
                    if not isinstance(request_id, str) or \
                            not request_id or len(request_id) > 128:
                        raise ValueError("request_id must be a "
                                         "non-empty string of "
                                         "<= 128 chars")
                slo = req.get("slo")
                if slo is not None:
                    slo = str(slo)
                    # bounded metric cardinality: only configured
                    # classes may be tagged over the wire (scheduler-
                    # level callers can still register ad hoc)
                    known = self.sched.tele.slo_classes
                    if slo not in known:
                        raise ValueError(
                            f"unknown slo class {slo!r} (configured: "
                            f"{sorted(known)})")
            except (ValueError, KeyError, TypeError) as e:
                self._refuse(conn, f, {
                    "done": True, "n_tokens": 0,
                    "error": f"bad request: {type(e).__name__}: {e}"})
                return
            # clamp to slot capacity (prompt + gen must fit the slot);
            # a prompt with no room for even one token is refused here
            # with a visible error instead of occupying a slot
            slot_cap = self.sched.slots.capacity
            cap = slot_cap - len(ids)
            if cap < 1:
                self._refuse(conn, f, {
                    "done": True, "n_tokens": 0,
                    "error": f"prompt of {len(ids)} tokens exceeds "
                             f"capacity {slot_cap - 1}"})
                return
            gen_len = max(1, min(gen_len, cap))
            # the loop gives the rid: the inbox's order is the order of
            # the waiting line
            arrival = self._Arrival(Request(
                rid=None, ids=np.asarray(ids, np.int32),
                gen_len=gen_len, seed=seed, n=n, grammar=gspec,
                deadline_ms=deadline_ms, slo=slo,
                accepted_at=accepted_at), conn, f)
            with self._inbox_lock:
                if not self._closed:
                    self._inbox.append(arrival)
                    self._wake.set()
                else:
                    arrival.ready.set()
            arrival.ready.wait()
            if arrival.accepted is None:
                self._refuse(conn, f, {
                    "done": True, "n_tokens": 0,
                    "error": "server stopped before the request "
                             "was taken in"})
            elif not arrival.accepted:
                # backpressure, not an unbounded queue: tell the client
                # WHEN to come back instead of buffering it forever
                self._refuse(conn, f, {"busy": True,
                                       "retry_after_ms": arrival.hint})
        except OSError as e:
            print(f"[TokenServer] bad request: {type(e).__name__}: {e}",
                  file=sys.stderr)
            try:
                conn.close()
            except OSError:
                pass

    def _serve_metrics(self) -> None:
        """Prometheus text-exposition listener: one short-lived HTTP
        exchange per scrape (HTTP/1.0, connection-close — the format
        every Prometheus-compatible scraper speaks). Refreshes the
        point-in-time gauges via stats() before rendering, and serves
        the scheduler registry plus the process-global default (the
        Engine dispatch counters)."""
        from triton_dist_tpu.runtime.telemetry import (
            default_registry, prometheus_text)
        while not self._stop.is_set():
            try:
                conn, _ = self._msock.accept()
            except socket.timeout:
                continue
            except OSError:
                return
            try:
                conn.settimeout(2.0)
                conn.recv(4096)          # request line + headers
                self.stats()             # refresh registry gauges
                body = prometheus_text(self.sched.tele.registry,
                                       default_registry()).encode()
                conn.sendall(
                    b"HTTP/1.0 200 OK\r\n"
                    b"Content-Type: text/plain; version=0.0.4; "
                    b"charset=utf-8\r\n"
                    b"Content-Length: " + str(len(body)).encode()
                    + b"\r\n\r\n" + body)
            except OSError:
                pass
            finally:
                try:
                    conn.close()
                except OSError:
                    pass

    def _byte_vocab(self):
        """Byte-string vocab for grammar compiles, built once per
        server against the model's vocab size (every grammar request
        shares it — compiling a JSON schema is cheap, rebuilding the
        vocab per request is not)."""
        if self._vocab is None:
            from triton_dist_tpu.models.structured import byte_vocab
            self._vocab = byte_vocab(self.sched.slots._vocab_size)
        return self._vocab

    def _retry_after_ms(self) -> int:
        """Backpressure hint: the measured poll cadence times the line
        ahead of the client — crude, but it scales with actual load
        instead of being a magic constant."""
        depth = self.sched.queue_depth
        return int(max(25.0, min(5000.0,
                                 1e3 * self._poll_ema * (depth + 2))))

    def _emit(self, rid, toks) -> None:
        """Stream one chunk's tokens to the owning client; a dead
        socket marks the stream dead — the model loop then CANCELS its
        slot (sched.cancel) instead of decoding to gen_len with the
        tokens falling on the floor."""
        cs = self._conns.get(rid)
        if cs is None or cs.dead:
            return
        row = [int(t) for t in toks]
        msg = {"text": self.tok.decode(row), "token_ids": row}
        if isinstance(rid, tuple):
            # fork kid rid (parent, k): tag the chunk so the client
            # can demux the n interleaved streams
            msg["fork"] = int(rid[1])
        try:
            cs.fh.write(json.dumps(msg) + "\n")
            cs.fh.flush()           # the stream is the point
            cs.n += len(row)
        except OSError:
            cs.dead = True
            return
        self.sched.tele.wire_first(rid, len(row))

    def _probe_disconnects(self) -> None:
        """Detect clients that hung up WITHOUT a failed write: after
        the request line a client never sends again, so a non-blocking
        recv returning b'' is EOF — mark the stream dead so the model
        loop cancels its slot this iteration."""
        for cs in list(self._conns.values()):
            if cs.dead:
                continue
            try:
                timeout = cs.conn.gettimeout()
            except OSError:
                cs.dead = True
                continue
            try:
                cs.conn.setblocking(False)
                if cs.conn.recv(1) == b"":
                    cs.dead = True
            except (BlockingIOError, InterruptedError):
                pass            # alive, nothing to read
            except OSError:
                cs.dead = True
            finally:
                try:
                    cs.conn.settimeout(timeout)   # keep the write timeout
                except OSError:
                    pass

    def stats(self) -> dict:
        """Serving counters: prefix-cache (hit rate, prefill tokens
        skipped — paged path), speculative decoding (spec_accept_rate,
        tokens_per_step — spec=K mode), the resilience counters
        (queue_depth, preemptions, deadline_expired, busy_rejections,
        "hang" verdict once a watchdogged chunk missed its deadline),
        the live ttft_ms / inter_token_ms / poll_ms histograms, and
        the process's compile accounting (program_compile_s / _n:
        trace, lower, backend and cache-load seconds and events per
        engine program; a compile inside the serving loop shows as
        program_compile_n rising after warm-up).

        The scheduler already returns a DEEP single-point-in-time
        registry snapshot (runtime/telemetry.py) — every container
        freshly allocated under the scheduler + registry locks — so
        cross-thread readers (this server's reader threads, the
        /metrics listener, test hammers) can iterate and serialize it
        while the driver keeps polling."""
        from triton_dist_tpu.runtime.telemetry import \
            install_compile_accounting
        with self._lock:
            st = self.sched.stats()
        # the process-wide compile accounting, flat as host_phase_s is:
        # {"<program role>/<stage>": seconds} and events
        st["program_compile_s"], st["program_compile_n"] = \
            install_compile_accounting().totals()
        if self.replica_id is not None:
            st["replica_id"] = self.replica_id
        return st

    def _finish(self, rid, error: Optional[str] = None) -> bool:
        """Close out one finished rid; returns True when the client
        stream fully closed. A forked request registers one stream
        under n kid rids — each kid's finish decrements the fan-in
        count and only the LAST writes the single done message."""
        cs = self._conns.pop(rid, None)
        if cs is None:
            return False
        reason = error if error is not None \
            else self.sched.rejected.pop(rid, None)
        if reason is not None:
            cs.errors.append(f"fork {rid[1]}: {reason}"
                             if isinstance(rid, tuple) else reason)
        cs.n_left -= 1
        if cs.n_left > 0:
            return False
        reason = "; ".join(cs.errors) if cs.errors else None
        try:
            if not cs.dead:
                msg = {"done": True, "n_tokens": cs.n}
                if self.replica_id is not None:
                    # fleet identity echo: the router feeds its shadow
                    # placement index from this retire event
                    msg["replica"] = self.replica_id
                if reason is not None:
                    # a scheduler-rejected request (pool exhausted,
                    # over capacity) must not look like a legitimate
                    # zero-token completion
                    msg["error"] = reason
                st = self.sched.stats()
                # host time per poll with device wait subtracted — the
                # overlap scheduler's observable win (the EMA the
                # operator compares overlap on vs off)
                msg["host_ms_per_poll"] = st["host_ms_per_poll"]
                if self.paged:
                    msg["cache"] = {
                        k: st[k] for k in ("hit_rate",
                                           "prefill_tokens_skipped",
                                           "prefill_skip_frac")}
                    if st.get("host_pool_pages"):
                        # host-tier gauges: the operator's live view
                        # of demote/promote behaviour per reply
                        msg["cache"].update({
                            k: st[k] for k in ("host_hits",
                                               "host_pages_resident",
                                               "demotions",
                                               "promotions",
                                               "restore_latency_ms")})
                cs.fh.write(json.dumps(msg) + "\n")
                cs.fh.flush()
        except OSError:
            pass
        for closer in (cs.fh.close, cs.conn.close):
            try:
                closer()
            except OSError:
                pass
        return True

    def _acceptor(self) -> None:
        """The thread that owns accept(): it blocks there (a time-out
        of its own, so `_stop` is seen) and hands every connection,
        stamped, to a reader thread (daemonic and short-lived: one
        request line each, no tracking needed). `self._sock` is read
        anew every pass: a caller may wrap the listener of a RUNNING
        server (benchmark/systems/token_server.py::annotate). It opens
        no phase: `host_phase_s` partitions the serve thread's time.
        Ends with the loop, whose teardown closes the listener."""
        while not self._stop.is_set():
            try:
                conn, _ = self._sock.accept()
            except socket.timeout:
                continue
            except OSError:
                return
            threading.Thread(target=self._reader,
                             args=(conn, time.monotonic()),
                             daemon=True).start()

    def _intake(self) -> int:
        """Serve thread: take in what the readers parsed since the
        last intake, in arrival order: a rid, the scheduler's verdict,
        and for an accepted request its `_conns` entry, which so exists
        before any poll can emit for it. Returns how many it took."""
        self._wake.clear()      # before the drain: a put during it
        taken = 0               # is either drained or wakes the loop
        while self._inbox:
            arrival = self._inbox.popleft()
            req = arrival.req
            req.rid = rid = self._next_rid
            self._next_rid += 1
            arrival.accepted = self.sched.submit(req)
            if arrival.accepted:
                cs = self._ClientStream(arrival.conn, arrival.fh)
                cs.n_left = req.n
                if req.n > 1:
                    # the scheduler fans rid out into kid rids
                    # (rid, 0)..(rid, n-1); every fork streams to
                    # this ONE connection and the done message
                    # fans back in once all n finish
                    for k in range(req.n):
                        self._conns[(rid, k)] = cs
                else:
                    self._conns[rid] = cs
            else:
                arrival.hint = self._retry_after_ms()
            arrival.ready.set()
            taken += 1
        return taken

    def serve_forever(self, max_requests: Optional[int] = None) -> None:
        """Model loop: take in the requests that arrived (an acceptor
        thread hands each connection to a reader thread, which parses
        it into the inbox), then run the scheduler — admit, one chunk,
        stream each slot's tokens to its client. The loop never waits
        on a timer for connections: with a chunk in flight it goes
        from the intake straight to the poll, whose `land` waits for
        the device; with nothing in flight, or after an iteration that
        took in, landed, emitted and finished nothing (a disaggregated
        prefill still out, a queued request that cannot be admitted
        yet), it sleeps on the inbox's wake event, bounded so `_stop`
        is seen. max_requests counts COMPLETED requests (so a test can
        serve N concurrent clients and exit). A watchdogged chunk that
        hangs (watchdog_s) ends the loop with a structured HANG error
        to every live client — the process is poisoned
        (runtime/stress.py::watchdog contract), and a visible verdict
        beats a silent freeze."""
        from triton_dist_tpu.runtime.stress import HangError
        done_count = 0
        tele = self.sched.tele
        slots = self.sched.slots
        self._sock.settimeout(0.25)
        self._accept_thread = threading.Thread(
            target=self._acceptor, daemon=True, name="serve-acceptor")
        self._accept_thread.start()
        try:
            while not self._stop.is_set():
                self._c_iterations.inc()
                # one root phase per iteration; what no child phase
                # names is its own self time, so the phases' totals
                # partition this thread's wall time
                with tele.phase("loop"):
                    with tele.phase("accept_wait"):
                        took = self._intake()
                    t0 = time.monotonic()
                    waited = slots.device_wait_s
                    try:
                        with tele.phase("poll"), self._lock:
                            out, finished = self.sched.poll()
                    except HangError as e:
                        for rid in list(self._conns):
                            self._finish(rid, error=str(e))
                        break
                    self._poll_ema = 0.9 * self._poll_ema + \
                        0.1 * (time.monotonic() - t0)
                    with tele.phase("wire_write"):
                        for rid, toks in out.items():
                            self._emit(rid, toks)
                        for rid in finished:
                            if self._finish(rid):
                                done_count += 1
                    # cancel-on-disconnect: a hung-up client's slot
                    # retires NOW (pages freed / inserted into the
                    # prefix tree) instead of decoding to gen_len for
                    # nobody
                    with tele.phase("probe"):
                        self._probe_disconnects()
                        dead = [rid for rid, cs
                                in list(self._conns.items()) if cs.dead]
                        for rid in dead:
                            with self._lock:
                                self.sched.cancel(rid)
                            if self._finish(rid):
                                done_count += 1
                    if max_requests is not None \
                            and done_count >= max_requests:
                        break
                    idle = self.sched.idle
                    moved = took or out or finished or dead \
                        or slots.device_wait_s != waited
                    if (idle or not moved) and not self._inbox:
                        # nothing in flight, or nothing that this
                        # iteration could move (the poll waited for
                        # no device result either): sleep until a
                        # request arrives instead of spinning
                        with tele.phase("idle_sleep"):
                            self._wake.wait(0.05 if idle else 0.02)
                            if self._inbox:
                                self._c_idle_wakeups.inc()
        finally:
            # the listener first, so that probes and new clients are
            # refused at once; shutdown() wakes the acceptor's accept()
            for end in (lambda: self._sock.shutdown(socket.SHUT_RDWR),
                        self._sock.close):
                try:
                    end()
                except OSError:
                    pass
            self._accept_thread.join(timeout=2.0)
            with self._inbox_lock:
                self._closed = True
                unserved = list(self._inbox)
                self._inbox.clear()
            for arrival in unserved:    # its reader tells the client
                arrival.ready.set()
            for rid in list(self._conns):
                self._finish(rid)
            # TDTPU_TRACE contract: dump the poll-loop timeline +
            # request traces + metrics snapshot on exit (perfetto-
            # loadable; summarize with tools/trace_view.py)
            path = os.environ.get("TDTPU_TRACE")
            if path:
                try:
                    self.sched.dump_trace(path)
                except OSError:
                    pass

    def stop(self) -> None:
        self._stop.set()
        self._wake.set()        # a sleeping loop sees _stop now
        # disaggregated mode: stop the prefill worker threads too
        close = getattr(self.sched, "close", None)
        if close is not None:
            close()
        if self._msock is not None:
            try:
                self._msock.close()
            except OSError:
                pass


def full_jitter(delay_s: float, rand=None) -> float:
    """Full-jitter backoff (AWS architecture-blog flavor): a uniform
    draw over [0, delay_s]. The deterministic alternative — sleeping
    exactly delay_s — means N clients that failed TOGETHER (a router
    death severs every stream at once) retry together forever, each
    round a synchronized thundering herd; the uniform draw decorrelates
    them in one round. ``rand`` is an injectable () -> [0, 1) for
    distribution tests (tests/test_serving.py)."""
    if rand is None:
        rand = random.random
    return max(0.0, float(delay_s)) * rand()


def request_stream(host: str, port: int, prompt: str, *,
                   gen_len: int = 16, seed: int = 0,
                   timeout: float = 300.0,
                   deadline_ms: Optional[float] = None,
                   slo: Optional[str] = None,
                   session: Optional[str] = None,
                   request_id: Optional[str] = None,
                   n: int = 1, grammar: Optional[dict] = None,
                   connect_retries: int = 8,
                   connect_backoff_s: float = 0.05,
                   busy_retries: int = 4) -> Iterator[dict]:
    """Client: send one prompt, yield the server's chunk messages as
    they arrive (the last one has {"done": true}, possibly carrying an
    "error" — rejection, deadline expiry, server hang — which callers
    should check rather than trusting n_tokens). Reference: the chat.py
    client's receive loop.

    n>1 requests parallel sampling (KV fork server-side): chunk
    messages then carry a "fork" index to demux the n interleaved
    streams, and ONE fan-in done message closes them all. grammar= is
    passed through as the wire spec ({"type": "json_schema", ...} or
    {"type": "token_fsm", ...}) for constrained decoding.

    Resilient by default: a refused connect (server still starting —
    the classic flaky-test source) retries with bounded exponential
    backoff, and a {"busy": ...} backpressure reply sleeps the server's
    retry_after_ms hint and resubmits, up to busy_retries times before
    raising ServerBusy. Busy replies are consumed internally — they are
    NEVER yielded as chunks."""
    payload = {"prompt": prompt, "gen_len": gen_len, "seed": seed}
    if n != 1:
        payload["n"] = n
    if grammar is not None:
        payload["grammar"] = grammar
    if deadline_ms is not None:
        payload["deadline_ms"] = deadline_ms
    if slo is not None:
        payload["slo"] = slo
    if session is not None:
        # affinity hint: a bare server validates and ignores it; a
        # fleet router (fleet/router.py) pins the session to a replica
        payload["session"] = session
    if request_id is not None:
        # idempotency key: a bare server validates and ignores it; a
        # fleet router dedups on it (fleet/ha.py) so a retried submit
        # after an ambiguous EOF never double-serves
        payload["request_id"] = request_id
    connects = 0
    busy_left = busy_retries
    while True:
        try:
            s = socket.create_connection((host, port), timeout=timeout)
        except OSError:
            if connects >= connect_retries:
                raise
            # full jitter: every client that lost its router at the
            # same instant must NOT reconnect at the same instant
            time.sleep(full_jitter(
                min(connect_backoff_s * (2 ** connects), 2.0)))
            connects += 1
            continue
        retry_ms = None
        with s, s.makefile("rw") as f:
            f.write(json.dumps(payload) + "\n")
            f.flush()
            for line in f:
                msg = json.loads(line)
                if msg.get("busy"):
                    retry_ms = float(msg.get("retry_after_ms", 100.0))
                    break
                yield msg
                if msg.get("done"):
                    return
            else:
                return      # server closed without a done message
        if retry_ms is None:
            return
        if busy_left <= 0:
            raise ServerBusy(retry_ms)
        busy_left -= 1
        time.sleep(full_jitter(retry_ms / 1e3))
