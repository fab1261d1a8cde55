"""Prefill/decode disaggregation: dedicated prefill workers stream KV
pages to decode workers over the p2p tier (ROADMAP open item 1, second
half — the DistServe split, Zhong et al. 2401.09670; Mooncake's
KV-centric formulation of the same argument, PAPERS.md).

WHY: chunked prefill (models/scheduler.py step_mixed) BOUNDS the stall
a long admission's prefill puts on live decode streams, but does not
remove it — every mixed tick still carries up to `prefill_budget`
prompt tokens through the decode mesh's forward, so prefill traffic
sets the inter-token floor whenever admissions are hot. The production
topology separates the two regimes onto different hardware: PREFILL
WORKERS (compute-bound, batch=1 long forwards) compute a prompt's KV
into a staging paged pool and push the finished pages to the
DECODE workers (bandwidth-bound, q_len=1 forever), which install the
pages and arm the slot. Decode ticks never see a prefill q_len again:
`stats()["max_prefill_tokens_per_poll"]` is structurally 0 on the
decode mesh, and the measured win is `inter_token_p99_ms` under
long-prompt load.

THE TRANSFER PLANE — a transferred page is a demoted page with a
different destination: the PR-6 host-tier serialization pair
(`Engine.extract_pages_host` one-DMA gather / `restore_pages_host`
one-DMA scatter, raw pool-dtype bytes so the round trip is bitwise,
int8 scale planes riding the same ids, PR-9 owning-plane selection on
TP-sharded pools) is reused unchanged as the wire format. Transports:

- `HostTransport` (default): the extract/restore pair IS the
  transfer — d2h off the prefill pool, h2d into the decode pool
  (the same-host smoke, and the fallback tier anywhere).
- `ICITransport`: the payload rides `kernels/p2p.p2p_push_pages` —
  the paper's one-sided neighbor-put kernel (`p2p_shift`) hopping the
  bytes from the prefill chip's plane to the decode chip's over ICI.
- `DCNTransport`: cross-slice push via `kernels/two_tier.
  kv_push_slices` — the XLA-collective tier of the two-tier design
  (DCN has no one-sided semantics; the slice hop is a ppermute).

BITWISE CONTRACT (tests/test_disagg.py): the prefill worker runs the
SAME bucketed prefill program the fused admission runs
(`admit_slot_paged` at kv_start=0), the extract/restore pair moves raw
bytes, and the decode-side install maps the transferred pages exactly
where a fused admission's freshly written pages would sit — so decode
token streams are bitwise identical disagg vs fused across {greedy,
sampled, spec=K} x {prefix cache, preemption, host tier}, same tokens,
same PRNG chains, with ZERO new XLA programs per decode poll (the
install reuses the install/restore executables that already exist for
chunked admission and the host tier).

SCHEDULING (DisaggScheduler): admission becomes two-pool —
1. ROUTE: a fresh request leaves the queue for the prefill plane
   (no decode slot is held while it prefills); a RESUMED request
   (preemption) re-admits decode-side directly — its pages are in the
   radix tree, so the "prefill" is the 1-token suffix recompute.
2. PREFILL: a worker computes the FULL prompt KV into its own staging
   pool and extracts the page payload + the arming logits row. The
   staging pool is released in the same job (zero-leak on BOTH pools:
   `available + outstanding == num_pages` holds on the staging AND
   decode allocators — tests/test_disagg.py chaos matrix).
3. PUSH: the payload crosses the transfer plane (`kv_push` trace
   instant; `pages_transferred`/`transfer_bytes` counters;
   fault-injectable — runtime/chaos.py transfer faults: a DROPPED
   push re-queues the request to prefill, a DUPLICATED push is
   discarded idempotently at install, a prefill-worker DEATH
   mid-transfer releases staging and retries).
4. INSTALL: the decode side runs the normal `_reserve_pages` flow
   (prefix lookup, refcounts, eviction, CoW bookkeeping), restores
   the transferred payload into the fresh pages covering the
   uncached extent, installs the table, inserts the prompt into the
   radix tree (a transferred prefix is immediately shareable) and
   arms the slot with the transferred logits (`kv_install` instant,
   `kv_transfer_latency_ms` histogram). Pool pressure at install
   walks the SAME preempt-or-wait ladder as fused admission.

TTFT overlaps transfer with the tail of prefill: the push happens the
moment extraction lands, while other requests' prefills queue behind —
and with `threads=True` the prefill plane runs on its own thread(s),
so decode polls never block on a prefill forward at all (the CPU smoke
approximation of dedicated prefill chips; on a real deployment each
worker is its own mesh slice and `transport` picks ICI or DCN).

OBSERVABILITY (runtime/telemetry.py — PR 11): a disaggregated trace
is ONE merged timeline. Each prefill worker owns a named track
(`prefill:compute` / `kv_push` spans — inline and threaded alike),
and a request's trace context (`KVHandoff.flow_id`) propagates across
the transfer wire so its journey draws as a Chrome flow-arrow chain
route -> prefill compute -> kv_push -> kv_install joining both
planes; `tools/trace_view.py` reports per-plane time and per-request
transfer latency. The staging pools are gauge-visible per worker
(`staging_pages_resident{worker=...}` — 0 at idle IS the zero-leak
invariant — plus peak/occupancy), prefill-plane and transfer device
time land in their own `device_wait_s_by_kind` buckets, and SLO
classes (`Request.slo`) ride through unchanged. All host-side only:
trace-on == trace-off bitwise with zero new XLA programs
(tests/test_disagg.py churn guard, tests/test_observability.py).

When fused chunked prefill is still the right call: see the README
"Disaggregated serving" section — at low admission rates or tiny
prompts the transfer latency buys nothing and one mesh is simpler.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from collections import deque
from typing import Dict, List, Optional

import numpy as np

from triton_dist_tpu.models.scheduler import (ContinuousScheduler,
                                              Request, _TokenLog)


class PrefillWorkerDied(RuntimeError):
    """A prefill worker failed mid-job (chaos: runtime/chaos.py
    FaultInjector.kill_prefills). The job's staging pages are released
    by the worker's own cleanup and the request re-queues to the
    prefill plane — the decode mesh never notices."""


@dataclasses.dataclass
class KVHandoff:
    """One finished prefill in flight to the decode mesh: the request,
    the prompt's page payload in extract_pages_host wire format
    (k/v [L, npp, Hkv, page, d] raw pool-dtype bytes, ks/vs scale
    planes when the pool is int8), and the arming logits row the
    decode slot needs (the fused admission gets it from the same
    forward — the device transports ship it alongside the pages).
    `t_push` stamps the push for kv_transfer_latency_ms. `flow_id` is
    the request's TRACE CONTEXT, propagated across the prefill ->
    decode transfer wire: the decode-side install ends the same
    Chrome-trace flow chain the prefill plane started, so ONE merged
    trace shows the request's journey across both planes (0 = tracing
    off, no chain)."""
    req: Request
    n: int                              # prompt length
    npp: int                            # prompt pages staged
    payload: Dict[str, Optional[np.ndarray]]
    logits_row: np.ndarray              # [V] f32
    t_push: float = 0.0
    flow_id: int = 0

    def wire_arrays(self) -> Dict[str, Optional[np.ndarray]]:
        """Everything a device transport must move: the page payload
        AND the arming logits row (a decode worker on another chip
        cannot arm the slot from bytes that never crossed)."""
        return dict(self.payload, logits=self.logits_row)

    def with_wire(self, moved: Dict[str, Optional[np.ndarray]]
                  ) -> "KVHandoff":
        """Rebuild from a transport's moved arrays."""
        row = moved.pop("logits")
        return dataclasses.replace(self, payload=moved, logits_row=row)


class HostTransport:
    """The default (same-host / fallback) transfer tier: the payload
    is already serialized host bytes (extract_pages_host), so the push
    is the identity — d2h off the staging pool and h2d into the decode
    pool ARE the transfer. Exists so the fault hooks, counters and
    trace instants wrap one seam whatever the tier."""

    name = "host"

    def push(self, handoff: KVHandoff) -> KVHandoff:
        return handoff


class ICITransport:
    """On-slice device path: every payload array rides
    kernels/p2p.p2p_push_pages — the paper's one-sided neighbor-put
    kernel (`p2p_shift`) — from the prefill chip's mesh position to
    the decode chip's. Bitwise: the kernel moves raw bytes
    (tests/test_disagg.py pins payload equality through the hop)."""

    name = "ici"

    def __init__(self, mesh, *, axis: str = "tp", src: int = 0,
                 dst: Optional[int] = None):
        n = mesh.shape[axis]
        self.mesh, self.axis = mesh, axis
        self.src = int(src) % n
        self.dst = (self.src + 1) % n if dst is None else int(dst) % n

    def push(self, handoff: KVHandoff) -> KVHandoff:
        from triton_dist_tpu.kernels.p2p import p2p_push_pages
        moved = {
            k: (None if a is None else np.asarray(p2p_push_pages(
                a, mesh=self.mesh, axis=self.axis, src=self.src,
                dst=self.dst)))
            for k, a in handoff.wire_arrays().items()}
        return handoff.with_wire(moved)


class DCNTransport:
    """Cross-slice device path: the payload crosses the slice boundary
    via kernels/two_tier.kv_push_slices — an XLA ppermute on the DCN
    axis, the tier XLA owns (two_tier.py design rule: one-sided Pallas
    inside a slice, XLA collectives across slices)."""

    name = "dcn"

    def __init__(self, mesh, *, slice_axis: str = "dcn", src: int = 0,
                 dst: Optional[int] = None):
        n = mesh.shape[slice_axis]
        self.mesh, self.slice_axis = mesh, slice_axis
        self.src = int(src) % n
        self.dst = (self.src + 1) % n if dst is None else int(dst) % n

    def push(self, handoff: KVHandoff) -> KVHandoff:
        from triton_dist_tpu.kernels.two_tier import kv_push_slices
        moved = {
            k: (None if a is None else np.asarray(kv_push_slices(
                a, mesh=self.mesh, slice_axis=self.slice_axis,
                src=self.src, dst=self.dst)))
            for k, a in handoff.wire_arrays().items()}
        return handoff.with_wire(moved)


def _sibling_engine(engine):
    """A prefill-plane Engine over the SAME model (weights shared
    read-only, jitted programs shared process-wide via
    engine._jit_programs) but with its OWN mutable scratch state, so a
    threaded prefill worker never races the decode engine's
    per-instance scratch caches. On a real deployment this is the
    worker's own mesh slice; on the smoke it is the same chips."""
    from triton_dist_tpu.models.engine import Engine
    p = engine._sample_params
    return Engine(engine.model, max_seq=engine.max_seq,
                  backend=engine.backend,
                  prefill_backend=engine.prefill_backend,
                  kv_dtype=engine.kv_dtype, sampling=engine.sampling,
                  temperature=p["temperature"], top_k=p["k"],
                  top_p=p["p"])


class PrefillWorker:
    """One dedicated prefill worker: its own staging paged pool + the
    existing bucketed prefill program (`Engine.admit_slot_paged` at
    kv_start=0 — the SAME executable the fused admission runs, which
    is what makes the handoff bitwise), one job at a time. A job
    allocates the prompt's pages, runs the forward, extracts the
    payload (+ arming logits) and ALWAYS releases the staging pages —
    the staging allocator's zero-leak invariant
    (available + outstanding == num_pages) holds between jobs even
    under injected worker death (tests/test_disagg.py)."""

    def __init__(self, engine, *, page: int = 16,
                 num_pages: Optional[int] = None, fault=None,
                 name: str = "prefill-worker-0"):
        from triton_dist_tpu.models.prefix_cache import RefcountedPages
        self.engine = engine
        self.page = page
        self.name = name             # trace track + gauge label
        # for_ticks=False: the staging pool only runs the bucketed
        # admit forward (whose row count is the EP-aligned pad bucket),
        # never a decode tick — the MoE-family batch gate must not
        # refuse a 1-slot staging pool on an EP mesh
        self.cache = engine.make_paged_slot_cache(1, page=page,
                                                  num_pages=num_pages,
                                                  for_ticks=False)
        self.pool = RefcountedPages(self.cache.num_pages)
        assert self.pool.trash == self.cache.trash
        self.fault = fault
        self.prefill_tokens = 0      # prompt tokens this worker forwarded
        # staging-pool visibility (the decode pool's gauges exist; this
        # is the other half of the zero-leak invariant): pages held NOW
        # (0 between jobs — a nonzero idle value IS a leak) and the
        # high-water mark across jobs, surfaced per worker by
        # DisaggScheduler.stats()
        self.pages_peak = 0
        # wall time this worker spent blocked on its plane's device
        # programs (prefill forward + payload extraction) — the
        # "prefill" bucket of device_wait_s_by_kind
        self.device_s = 0.0

    @property
    def capacity(self) -> int:
        """Longest prompt one job can stage."""
        usable = self.pool.num_pages - 1
        return min(self.cache.capacity, usable * self.page)

    def prefill(self, req: Request) -> KVHandoff:
        """Run one job: full-prompt prefill into staging pages, then
        extract the payload in the host-tier wire format and the
        arming logits. Staging pages are released on every exit
        path."""
        import jax
        tokens = np.asarray(req.ids, np.int32).reshape(-1)
        n = len(tokens)
        if n == 0:
            raise ValueError(f"request {req.rid!r}: empty prompt")
        if n > self.capacity:
            raise ValueError(
                f"request {req.rid!r}: prompt {n} exceeds prefill "
                f"staging capacity {self.capacity}")
        npp = -(-n // self.page)
        pages: List[int] = []
        t_dev = time.perf_counter()
        try:
            for _ in range(npp):
                pages.append(self.pool.alloc_page())
            if self.pool.pages_in_use > self.pages_peak:
                self.pages_peak = self.pool.pages_in_use
            trash = self.cache.trash
            rows = np.full((self.cache.table.shape[1],), trash, np.int32)
            rows[:npp] = pages
            row, self.cache = self.engine.admit_slot_paged(
                self.cache, 0, tokens, rows, 0, trash, trash, 0)
            if self.fault is not None and getattr(
                    self.fault, "prefill_worker", None) is not None \
                    and self.fault.prefill_worker(req.rid):
                raise PrefillWorkerDied(
                    f"request {req.rid!r}: prefill worker killed "
                    f"mid-transfer (chaos injection)")
            out = self.engine.extract_pages_host(
                self.cache, np.asarray(pages, np.int32))
            payload = dict(zip(("k", "v", "ks", "vs"), out))
            payload.setdefault("ks", None)
            payload.setdefault("vs", None)
            logits_np = np.asarray(jax.device_get(row), np.float32)
        finally:
            self.device_s += time.perf_counter() - t_dev
            for g in pages:
                self.pool.release(g)
        self.prefill_tokens += n
        return KVHandoff(req=req, n=n, npp=npp, payload=payload,
                         logits_row=logits_np)


class DisaggScheduler(ContinuousScheduler):
    """ContinuousScheduler in DISAGGREGATED mode (module docstring):
    the decode mesh runs pure decode ticks while a prefill plane —
    `prefill_workers` PrefillWorker instances, inline (deterministic,
    the default) or on their own threads (`threads=True`) — computes
    admissions' KV and streams the pages across `transport`. Always
    paged (the page-granular pool IS what makes the transfer cheap);
    `prefill_budget` is meaningless here and rejected — chunked
    prefill is the fused alternative this mode replaces.

    Decode streams are bitwise identical to the fused scheduler at the
    same seeds (tests/test_disagg.py), so every downstream mode —
    sampled chains, spec=K, preemption/resume, host tier, overlap —
    composes unchanged."""

    def __init__(self, engine, *, batch: int, chunk: int = 4,
                 prefix_cache: bool = True, page: int = 16,
                 num_pages: Optional[int] = None, spec: int = 0,
                 drafter=None, max_queue: Optional[int] = None,
                 watchdog_s: Optional[float] = None,
                 preempt: bool = True, fault=None,
                 host_pool_pages: int = 0, overlap: bool = False,
                 telemetry=None, trace: Optional[bool] = None,
                 prefill_workers: int = 1, threads: bool = False,
                 transport=None, staging_pages: Optional[int] = None,
                 prefill_jobs_per_poll: int = 1,
                 slo_classes: Optional[dict] = None):
        """prefill_workers: dedicated prefill workers, each with its
        own staging pool and engine facade — a THREAD-MODE knob.
        threads=True runs them on daemon threads so decode polls never
        block on a prefill forward (call close() — or let
        TokenServer.stop() do it — when done); threads=False (default)
        services up to `prefill_jobs_per_poll` jobs inline per poll on
        ONE worker (serial on the driver thread, so extra workers
        would only be extra idle staging pools), deterministic for the
        differential tests. transport: HostTransport (default),
        ICITransport or DCNTransport. staging_pages sizes each
        worker's staging pool (default: one full slot)."""
        if prefill_workers < 1:
            raise ValueError(f"prefill_workers must be >= 1, got "
                             f"{prefill_workers}")
        engine.refuse_slot_state(
            "disaggregated serving",
            "state handoff: the prefill plane ships KV pages only")
        super().__init__(engine, batch=batch, chunk=chunk, paged=True,
                         prefix_cache=prefix_cache, page=page,
                         num_pages=num_pages, spec=spec, drafter=drafter,
                         max_queue=max_queue, watchdog_s=watchdog_s,
                         preempt=preempt, fault=fault,
                         host_pool_pages=host_pool_pages,
                         overlap=overlap, telemetry=telemetry,
                         trace=trace, slo_classes=slo_classes)
        self.engine = engine
        self.transport = transport if transport is not None \
            else HostTransport()
        self.threads = bool(threads)
        self.prefill_jobs_per_poll = int(prefill_jobs_per_poll)
        # the prefill plane: queue of routed requests, arrived
        # handoffs, and the ownership set (_pending maps every rid the
        # plane currently owns — queued, computing, or in transfer —
        # to its Request; an arrival whose rid is no longer pending is
        # a duplicate or a cancelled/expired transfer and is discarded
        # idempotently). One condition guards all three; lock order is
        # always scheduler._lock OUTSIDE _pf_cond.
        self._pf_cond = threading.Condition()
        self._prefill_q: deque = deque()
        self._transfers: deque = deque()
        self._pending: Dict[object, Request] = {}
        self._async_done: deque = deque()   # worker-thread rejects
        # inline mode serializes every job on the driver thread, so
        # extra workers would only be extra idle staging pools —
        # build one (prefill_workers is a thread-mode knob)
        n_workers = prefill_workers if self.threads else 1
        self._workers = [
            PrefillWorker(_sibling_engine(engine) if self.threads
                          else engine, page=page,
                          num_pages=staging_pages, fault=fault,
                          name=f"prefill-worker-{i}")
            for i in range(n_workers)]
        # cross-plane trace context: rid -> flow id, allocated at
        # ROUTING when tracing is on; the id rides the KVHandoff over
        # the transfer wire and the decode-side install ends the chain
        # (route -> prefill compute -> kv_push -> kv_install as flow
        # arrows in ONE merged trace). Mutations under _pf_cond.
        self._flow_ids: Dict[object, int] = {}
        self._flow_seq = 0
        reg = self.tele.registry
        reg.gauge("disagg", "1 = prefill/decode disaggregation on"
                  ).set(1)
        reg.gauge("prefill_workers").set(n_workers)
        self._h_transfer = reg.histogram(
            "kv_transfer_latency_ms",
            "KV page push -> decode-side install, per transfer")
        self._c_transfers = reg.counter(
            "kv_transfers", "page payloads installed on the decode "
                            "mesh")
        self._c_pages = reg.counter(
            "pages_transferred", "physical pages pushed across the "
                                 "transfer plane")
        self._c_bytes = reg.counter(
            "transfer_bytes", "payload bytes pushed across the "
                              "transfer plane")
        self._c_drops = reg.counter(
            "transfer_drops", "pushes lost in flight (chaos/fabric)")
        self._c_dups = reg.counter(
            "transfer_dups", "duplicate pushes delivered")
        self._c_discards = reg.counter(
            "transfers_discarded", "arrivals dropped at install "
                                   "(duplicate / cancelled / expired)")
        self._c_retries = reg.counter(
            "transfer_retries", "requests re-queued to prefill after "
                                "a failed transfer")
        self._c_deaths = reg.counter(
            "prefill_worker_deaths", "workers lost mid-job")
        self._c_plane_tokens = reg.counter(
            "prefill_plane_tokens", "prompt tokens forwarded on the "
                                    "prefill plane (off the decode "
                                    "mesh)")
        self._stop_workers = False
        self._threads: List[threading.Thread] = []
        if self.threads:
            for i, w in enumerate(self._workers):
                t = threading.Thread(target=self._worker_loop,
                                     args=(w,), daemon=True,
                                     name=f"prefill-worker-{i}")
                t.start()
                self._threads.append(t)

    # ------------------------------------------------------------------
    # prefill plane
    # ------------------------------------------------------------------

    def close(self) -> None:
        """Stop the worker threads (no-op inline). Idempotent."""
        self._stop_workers = True
        with self._pf_cond:
            self._pf_cond.notify_all()
        for t in self._threads:
            t.join(timeout=5.0)
        self._threads = []

    def _worker_loop(self, worker: PrefillWorker) -> None:
        while not self._stop_workers:
            with self._pf_cond:
                while not self._prefill_q and not self._stop_workers:
                    self._pf_cond.wait(0.05)
                if self._stop_workers:
                    return
                req = self._prefill_q.popleft()
            self._run_prefill_job(worker, req)

    def _submit_prefill(self, req: Request, *, front: bool = False
                        ) -> None:
        """Hand a request to the prefill plane (rid must already be in
        _pending — a cancelled/expired rid silently drops here)."""
        with self._pf_cond:
            if req.rid not in self._pending:
                return
            (self._prefill_q.appendleft if front
             else self._prefill_q.append)(req)
            self._pf_cond.notify()

    def _run_prefill_job(self, worker: PrefillWorker, req: Request
                         ) -> None:
        """One job end-to-end: forward + extract (worker), fault
        consult, transport push, delivery. Runs on a worker thread
        (threads=True) or the driver thread (inline)."""
        rid = req.rid
        if rid not in self._pending:       # cancelled while queued
            return
        # cross-plane tracing: this job's spans land on the WORKER's
        # own timeline track, joined to the decode plane by the
        # request's flow chain (flow id allocated at routing)
        tele = self.tele
        tid = tele.track(worker.name) if tele.trace else 0
        fid = self._flow_ids.get(rid, 0)
        t_job = time.monotonic()
        try:
            handoff = worker.prefill(req)
        except PrefillWorkerDied:
            # staging released by the worker's cleanup; the request
            # retries — the decode mesh never noticed
            self._c_deaths.inc()
            self._c_retries.inc()
            self.tele.instant("prefill_worker_death", str(rid))
            self._submit_prefill(req, front=True)
            return
        except ValueError as e:
            with self._lock:
                with self._pf_cond:
                    self._pending.pop(rid, None)
                    self._flow_ids.pop(rid, None)
                self._reject(rid, str(e))
                self._async_done.append(rid)
            return
        handoff.flow_id = fid
        tele.span("prefill:compute", t_job, time.monotonic(), tid=tid,
                  args={"rid": str(rid), "tokens": handoff.n})
        if fid:
            tele.flow("kv_transfer", fid, phase="t", tid=tid,
                      args={"rid": str(rid)})
        self._c_plane_tokens.inc(handoff.n)
        action = None
        if self.fault is not None:
            tf = getattr(self.fault, "transfer", None)
            if tf is not None:
                action = tf(rid)
        if action == "drop":
            # the push was lost in flight: nothing reached the decode
            # mesh, staging is already released — re-queue to prefill
            self._c_drops.inc()
            self._c_retries.inc()
            self.tele.instant("kv_transfer_drop", str(rid))
            self._submit_prefill(req, front=True)
            return
        # stamp BEFORE the wire push: with the device transports the
        # push IS the transfer, and kv_transfer_latency_ms exists to
        # show an operator a slow fabric
        t_push = time.perf_counter()
        t_span = time.monotonic()
        handoff = self.transport.push(handoff)
        handoff.t_push = t_push
        if fid:
            tele.flow("kv_transfer", fid, phase="t", tid=tid,
                      args={"rid": str(rid), "at": "kv_push"})
        tele.span("kv_push", t_span, time.monotonic(), tid=tid,
                  args={"rid": str(rid),
                        "transport": getattr(self.transport, "name",
                                             "?")})
        self._c_pages.inc(handoff.npp)
        self._c_bytes.inc(sum(a.nbytes for a in
                              handoff.wire_arrays().values()
                              if a is not None))
        self.tele.instant("kv_push", str(rid), tid=tid)
        with self._pf_cond:
            self._transfers.append(handoff)
            if action == "dup":
                self._c_dups.inc()
                # installs only read the handoff, so the duplicate can
                # be the same object — the second arrival's rid is no
                # longer pending and discards idempotently
                self._transfers.append(handoff)
            self._pf_cond.notify_all()

    def _pop_transfer(self) -> Optional[KVHandoff]:
        """Next installable handoff; duplicate/cancelled/expired
        arrivals are discarded idempotently (their rid is no longer
        pending)."""
        with self._pf_cond:
            while self._transfers:
                h = self._transfers.popleft()
                if h.req.rid in self._pending:
                    return h
                self._c_discards.inc()
            return None

    def _validate(self, req: Request, tokens: np.ndarray) -> None:
        """Run at ROUTING so a request that can never be admitted is
        rejected before any prefill-plane work: the fused scheduler's
        own upfront refusals (ONE shared implementation —
        PagedDecodeSlots.validate_admission) plus the plane's staging
        bound."""
        self.slots.validate_admission(req, tokens)
        n = len(tokens)
        if n > self._workers[0].capacity:
            raise ValueError(
                f"request {req.rid!r}: prompt {n} exceeds prefill "
                f"staging capacity {self._workers[0].capacity}")

    # ------------------------------------------------------------------
    # decode-side install
    # ------------------------------------------------------------------

    def _install(self, slot: int, handoff: KVHandoff) -> None:
        """Admit a transferred prefill into a decode slot: the normal
        paged reservation (prefix lookup / refcounts / eviction), then
        table install + payload restore IN PLACE OF the boundary CoW +
        suffix forward — the transferred pages hold bytes the fused
        path would have computed (cache-on==off bitwise), so the
        stream cannot tell the difference. Raises PoolExhausted with
        everything released (the caller walks the preempt ladder)."""
        import jax.numpy as jnp
        slots = self.slots
        req, n = handoff.req, handoff.n
        tokens = np.asarray(req.ids, np.int32).reshape(-1)
        slot_pages, m, rows, _cs, _cd, r, boundary = \
            slots._reserve_pages(req, tokens)
        pool = slots.prefix.pool
        if boundary is not None:
            # the fused path CoWs the boundary page; here the whole
            # page arrives in the payload — the cached source is not
            # read at all
            pool.release(boundary)
        npp = -(-n // slots.page)
        full = m // slots.page
        t_dev = time.perf_counter()
        t_span = time.monotonic()
        try:
            trash = slots.cache.trash
            slots.cache = self.engine.install_slot_paged(
                slots.cache, slot, rows, trash, trash, 0)
            target = slot_pages[full:npp]
            if target:
                ids = np.asarray(target, np.int32)
                sl = slice(full, npp)
                pl = handoff.payload
                slots.cache = self.engine.restore_pages_host(
                    slots.cache, ids, pl["k"][:, sl], pl["v"][:, sl],
                    None if pl["ks"] is None else pl["ks"][:, sl],
                    None if pl["vs"] is None else pl["vs"][:, sl])
        except Exception:
            for g in slot_pages:
                pool.release(g)
            raise
        # the table install + payload restore are the transfer plane's
        # device programs — attributed to the "transfer" bucket of
        # device_wait_s_by_kind (the decode/verify buckets stay pure)
        slots.device_wait_by_kind["transfer"] = \
            slots.device_wait_by_kind.get("transfer", 0.0) \
            + (time.perf_counter() - t_dev)
        slots._pages[slot] = slot_pages
        slots._tokens[slot] = _TokenLog(tokens)
        slots.prefix.record(n, m)
        # a transferred prefix is immediately shareable: the next
        # admission — even one installing in the same poll — maps it
        slots.prefix.insert(tokens, slot_pages[:npp])
        slots._arm_slot(slot, req, jnp.asarray(handoff.logits_row), n)
        self._c_transfers.inc()
        if handoff.t_push:
            self._h_transfer.record(
                (time.perf_counter() - handoff.t_push) * 1e3)
        # end the cross-plane flow chain on the host track: the
        # kv_install span + "f" arrowhead the prefill plane's
        # kv_push points at (ONE merged trace, both planes)
        if handoff.flow_id:
            self.tele.flow("kv_transfer", handoff.flow_id, phase="f",
                           args={"rid": str(req.rid),
                                 "at": "kv_install"})
        self.tele.span("kv_install", t_span, time.monotonic(),
                       args={"rid": str(req.rid), "slot": slot})
        self.tele.instant("kv_install", str(req.rid))

    # ------------------------------------------------------------------
    # scheduler overrides
    # ------------------------------------------------------------------

    def _admit(self, done: List[object], out_acc=None) -> None:
        """Two-pool admission (module docstring): drain worker-thread
        rejects, ROUTE fresh queue heads to the prefill plane, run the
        inline prefill service (threads=False), then INSTALL arrived
        transfers / direct-admit resumed requests into free decode
        slots with the same preempt-or-wait ladder as fused
        admission. Runs under self._lock (the superclass callers hold
        it)."""
        from triton_dist_tpu.models.prefix_cache import PoolExhausted
        while self._async_done:
            done.append(self._async_done.popleft())
        # ROUTE: fresh requests leave the queue for the prefill plane
        # without waiting for a slot; resumed requests stay (they
        # re-admit decode-side below, FIFO with the transfers). With
        # max_queue set, the PLANE is bounded to max_queue requests
        # too — otherwise routing would drain the queue every poll and
        # submit()'s busy/{retry_after_ms} backpressure would never
        # fire while finished handoffs (whole prompt-KV payloads in
        # host RAM) piled up unboundedly behind full decode slots.
        i = 0
        while i < len(self._queue):
            if self.max_queue is not None \
                    and len(self._pending) >= self.max_queue:
                break
            req = self._queue[i]
            if req.resume is not None:
                i += 1
                continue
            tokens = np.asarray(req.ids, np.int32).reshape(-1)
            try:
                self._validate(req, tokens)
            except ValueError as e:
                del self._queue[i]
                self._reject(req.rid, str(e))
                done.append(req.rid)
                continue
            del self._queue[i]
            with self._pf_cond:
                self._pending[req.rid] = req
                if self.tele.trace:
                    # start the request's cross-plane flow chain on
                    # the host track (inside the bookkeep span): the
                    # worker's compute/push and the decode-side
                    # install continue it
                    self._flow_seq += 1
                    self._flow_ids[req.rid] = self._flow_seq
                    self.tele.flow("kv_transfer", self._flow_seq,
                                   phase="s",
                                   args={"rid": str(req.rid),
                                         "at": "route"})
            self._submit_prefill(req)
        # inline prefill service: the driver stands in for the worker
        # pool, bounded per poll so a deep admission burst cannot
        # starve the decode tick forever
        if not self.threads:
            for _ in range(self.prefill_jobs_per_poll):
                with self._pf_cond:
                    if not self._prefill_q:
                        break
                    req = self._prefill_q.popleft()
                self._run_prefill_job(self._workers[0], req)
        elif (not self.slots.occupied and not self._transfers
              and self._pending):
            # decode mesh idle, plane busy: yield briefly instead of
            # spinning the poll loop against the worker threads
            with self._pf_cond:
                if not self._transfers:
                    self._pf_cond.wait(0.002)
        # INSTALL: arrived transfers and resumed requests fill free
        # slots; pool pressure preempts an eligible victim (or waits)
        # exactly like the fused scheduler
        preempted_now: set = set()
        while True:
            free = self.slots.free
            if not free:
                return
            handoff = self._pop_transfer()
            if handoff is not None:
                rid = handoff.req.rid
                try:
                    if self.fault is not None:
                        self.fault.admission(handoff.req)
                    self._install(free[0], handoff)
                    with self._pf_cond:
                        self._pending.pop(rid, None)
                        self._flow_ids.pop(rid, None)
                    self.tele.req_event(rid, "admitted", free[0])
                    continue
                except PoolExhausted as e:
                    with self._pf_cond:
                        self._transfers.appendleft(handoff)
                    if self.overlap and not self._pipeline_idle():
                        self._drain(self._carry_out if out_acc is None
                                    else out_acc, done)
                        continue

                    def _drop_transfer(reason):
                        h = self._pop_transfer()
                        if h is None:
                            return
                        with self._pf_cond:
                            self._pending.pop(h.req.rid, None)
                            self._flow_ids.pop(h.req.rid, None)
                        self._reject(h.req.rid, reason)
                        done.append(h.req.rid)

                    if not self._preempt_for(rid, preempted_now,
                                             str(e),
                                             drop=_drop_transfer,
                                             requeue_at=0):
                        return
                    continue
                except ValueError as e:
                    with self._pf_cond:
                        self._pending.pop(rid, None)
                        self._flow_ids.pop(rid, None)
                    self._reject(rid, str(e))
                    done.append(rid)
                    continue
            if self._queue and self._queue[0].resume is not None:
                req = self._queue[0]
                try:
                    if self.fault is not None:
                        self.fault.admission(req)
                    self.slots.admit(free[0], req)
                    self._queue.popleft()
                    self.tele.req_event(req.rid, "resume", free[0])
                    continue
                except PoolExhausted as e:
                    if self.overlap and not self._pipeline_idle():
                        self._drain(self._carry_out if out_acc is None
                                    else out_acc, done)
                        continue

                    def _drop_resume(reason, req=req):
                        self._queue.popleft()
                        self._reject(req.rid, reason)
                        done.append(req.rid)

                    if not self._preempt_for(req.rid, preempted_now,
                                             str(e), drop=_drop_resume,
                                             requeue_at=1):
                        return
                    continue
                except ValueError as e:
                    self._queue.popleft()
                    self._reject(req.rid, str(e))
                    done.append(req.rid)
                    continue
            return

    # the PoolExhausted preempt-or-wait ladder is the inherited
    # ContinuousScheduler._preempt_for — ONE copy for both schedulers
    # (the install path passes requeue_at=0: its displacer is a
    # handoff, which installs ahead of the queue anyway)

    def _expire_deadlines(self, done: List[object]) -> None:
        """Fused expiry (queue + slots) plus the prefill plane: an
        expired rid anywhere in queue/compute/transfer is dropped with
        the usual visible reason; its arrival (if the payload was
        already in flight) is discarded idempotently at install."""
        super()._expire_deadlines(done)
        if not self._deadline:
            return
        now = time.monotonic()
        expired = {rid for rid, dl in self._deadline.items()
                   if now >= dl}
        if not expired:
            return
        victims: List[Request] = []
        with self._pf_cond:
            for rid in expired:
                req = self._pending.pop(rid, None)
                self._flow_ids.pop(rid, None)
                if req is not None:
                    victims.append(req)
            if victims:
                keep = deque(r for r in self._prefill_q
                             if r.rid not in expired)
                self._prefill_q = keep
        for req in victims:
            self._c_deadline_expired.inc()
            self._reject(req.rid,
                         f"deadline_ms={req.deadline_ms:g} expired "
                         f"during prefill/transfer",
                         status="expired")
            done.append(req.rid)

    def cancel(self, rid) -> bool:
        """Cancel-on-disconnect across all three pools: queued (super),
        owned by the prefill plane (dropped here — an in-flight
        payload's arrival discards idempotently), or in a decode slot
        (super)."""
        with self._lock:
            with self._pf_cond:
                if rid in self._pending:
                    self._pending.pop(rid)
                    self._flow_ids.pop(rid, None)
                    self._prefill_q = deque(
                        r for r in self._prefill_q if r.rid != rid)
                    self._deadline.pop(rid, None)
                    self.tele.retire(rid, "cancelled")
                    return True
        return super().cancel(rid)

    @property
    def idle(self) -> bool:
        return super().idle and not self._pending

    def stats(self) -> dict:
        reg = self.tele.registry
        # the prefill plane's device time rolls into the attribution
        # split BEFORE the superclass snapshots it (threads=True: this
        # is plane-busy time, not driver wait — same bucket either way)
        self.slots.device_wait_by_kind["prefill"] = round(
            sum(w.device_s for w in self._workers), 4)
        with self._lock, reg.lock:
            with self._pf_cond:
                reg.gauge("prefill_queue_depth",
                          "requests waiting for a prefill worker"
                          ).set(len(self._prefill_q))
                reg.gauge("transfers_in_flight",
                          "payloads pushed but not yet installed"
                          ).set(len(self._transfers))
                pend = len(self._pending)
            reg.gauge("prefill_pending",
                      "requests owned by the prefill plane").set(pend)
            # staging-pool gauges, per worker (decode pool gauges
            # already exist — this is the other half of the zero-leak
            # invariant made visible: resident must be 0 between jobs)
            staging_resident = 0
            staging_peak = 0
            for w in self._workers:
                usable = max(1, w.pool.num_pages - 1)  # minus trash
                in_use = w.pool.pages_in_use
                lb = {"worker": w.name}
                reg.gauge("staging_pages_resident",
                          "staging pages held right now (nonzero at "
                          "idle = leak)", labels=lb).set(in_use)
                reg.gauge("staging_pages_peak",
                          "staging high-water mark across jobs",
                          labels=lb).set(w.pages_peak)
                reg.gauge("staging_occupancy",
                          "resident / usable staging pages",
                          labels=lb).set(round(in_use / usable, 4))
                staging_resident += in_use
                staging_peak = max(staging_peak, w.pages_peak)
            out = super().stats()
        out.update({
            "disagg": True,
            "transport": getattr(self.transport, "name",
                                 type(self.transport).__name__),
            "prefill_workers": len(self._workers),
            "prefill_plane_tokens": self._c_plane_tokens.value,
            "kv_transfers": self._c_transfers.value,
            "pages_transferred": self._c_pages.value,
            "transfer_bytes": self._c_bytes.value,
            "transfer_drops": self._c_drops.value,
            "transfer_retries": self._c_retries.value,
            "prefill_worker_deaths": self._c_deaths.value,
            "staging_pages_resident": staging_resident,
            "staging_pages_peak": staging_peak,
        })
        return out
