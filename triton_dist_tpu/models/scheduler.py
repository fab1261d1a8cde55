"""Continuous-batching decode scheduler: slot-based multi-request
serving over the shared KV pool.

The reference's serving loop (`mega_triton_kernel/test/models/
model_server.py:265`) handles one prompt at a time, and the old
TokenServer tiled that single prompt across every decode row — B-1 of
B slots doing duplicate work in a regime that is weight-bandwidth
bound, where tok/s/chip scales with the number of DISTINCT occupied
slots. This module is the Orca-style iteration-level scheduler (the
role vLLM's continuous batching plays over paged attention —
PAPERS.md): up to `batch` concurrent requests occupy distinct decode
slots, a freed slot is refilled from the queue between chunked decode
scans, and the decode hot loop stays ONE XLA program per chunk shape
regardless of the occupancy mix — admission changes DATA (masks,
positions, per-slot keys), never the program.

Mechanics (engine.py slot path):
- each batch row of the cache is an independent slot; a new request
  prefills into a scratch row and is copied over its slot
  (Engine.prefill_into_slot) without touching live slots;
- decode chunks run Engine.slot_chunk: per-row sampling keyed by
  per-slot PRNG chains, per-row KV append at per-slot positions, and
  per-row attention lengths (flash_decode kv_lens) — so every slot's
  token chain is exactly a single-request Engine.serve() at its seed;
- between chunks the host trims each slot's tokens to its remaining
  budget, retires finished slots, and admits queued requests into the
  freed rows while the other slots keep decoding mid-stream.

Speculative decoding (spec=K, models/spec_decode.py): each step is one
draft-then-verify iteration instead of a 1-token scan step — the host
drafter proposes up to K continuations per slot by n-gram prompt
lookup, ONE verify forward (Engine.slot_verify_chunk /
paged_slot_verify_chunk) scores every slot's padded window, and each
slot emits its seed token plus the accepted prefix (1..K+1 tokens per
forward). Greedy streams stay bitwise identical to spec=0.

Chunked prefill (Sarathi-Serve, Agrawal et al. 2403.02310 — PAPERS.md):
with `prefill_budget` set, an admission no longer runs its prompt's
prefill as one monolithic program that stalls every live decode stream
for its duration (the head-of-line blocking Sarathi-Serve measures as
inter-token latency spikes). Instead the slot enters a PREFILLING
state (host-resumable offset into the uncached prompt suffix) and the
scheduler runs MIXED ticks: ONE forward per poll covers every live
decode slot (q_len = 1, or its spec window) AND up to `prefill_budget`
tokens of in-progress prefills (q_len = chunk), riding the per-slot
`q_lens`+`kv_lens` verify masks of kernels/flash_attn.py /
kernels/paged_kv.py. Chunk rows write their KV (contiguous columns or
pages) but emit a next-token logit only when the FINAL chunk lands —
the slot then arms (_arm_slot) and joins decode. The paged admission's
prefix-cache lookup and boundary-page copy-on-write happen ONCE at
chunk 0 (engine.install_slot_paged); the prompt is inserted into the
radix tree only when its KV is fully computed (arming), and a
preempted/cancelled mid-prefill slot donates exactly its VALID extent.
Streams are bitwise identical chunked vs monolithic across
{greedy, sampled, spec=K} x {contiguous, paged+prefix-cache}
(tests/test_chunked_prefill.py), and the maximum prefill work a live
stream waits on between its tokens drops from the full prompt length
to `prefill_budget` (stats(): max_prefill_tokens_per_poll).

Overlap scheduling (the SGLang zero-overhead overlap scheduler —
Zheng et al. 2312.07104, PAPERS.md — over this repo's slot machinery):
with ``ContinuousScheduler(overlap=True)`` the driver DISPATCHES the
device program for tick N+1 BEFORE reading back tick N's results, so
every poll's host bookkeeping (admit/retire, radix-tree inserts,
drafting, stats, the serving layer's socket writes) runs while the
device is busy — at large slot counts host time is otherwise the
inter-token floor. Mechanics:

- every blocking readback rides ``DecodeSlots._fetch`` and is timed
  into ``device_wait_s``, so ``stats()["host_ms_per_poll"]`` reports
  dispatch-to-dispatch host time with device wait subtracted (the EMA
  now lives as the ``host_ms_per_poll`` Gauge in the scheduler's
  metrics registry — runtime/telemetry.py — next to the live
  ``poll_ms``/``ttft_ms``/``inter_token_ms`` histograms); the
  tick's readback is ONE coalesced ``jax.device_get`` per poll (spec
  arming adds a small per-armed-slot seed fetch on top);
- the non-spec emission plan is HOST-DETERMINISTIC (each active slot
  emits min(remaining, chunk) tokens), so ``begin_chunk``/
  ``begin_mixed`` account budgets and clear finished slots' active
  masks at DISPATCH time and defer only the token VALUES — streaming,
  the paged token mirrors, and retirement (the radix-tree insert needs
  the values) — to ``land`` one poll later;
- spec=K drafts need the landed history, so the spec pipeline lands
  within its own poll and instead DEFERS the retire/admit work of the
  previous tick to run between dispatch and land (staged retires);
- admissions see a slot freed by tick N only after N lands — the
  one-tick admission delay — and any path that must mutate an
  in-flight slot (preemption, cancel-on-disconnect, an in-flight
  deadline expiry) DRAINS the pipeline first, so token streams stay
  BITWISE identical overlap-on vs overlap-off across every mode
  (tests/test_overlap.py);
- the watchdog and deadline checks move to LANDED-tick boundaries
  (a dispatch cannot hang; the readback can);
- ``TokenServer`` hands ``overlap=True`` down unless told otherwise
  (serving.py: dispatch-ahead is what the served path runs; this
  class's own default stays False for callers that step it poll by
  poll). Two registry counters say how the mechanism fares:
  ``ticks_dispatched_ahead`` (a spec=0 tick dispatched with the
  previous tick's retires, the server's wire writes and its next
  intake still to run under it; over the engine's dispatches of
  the same ticks, the share that engaged) and ``pipeline_drains``
  (every collapse: preemption, cancel, an in-flight deadline, a
  PoolExhausted admission, a grammar tick).

Resilience (the degradation ladder under pressure — vLLM's
preemption/recompute design over the Orca operational model,
PAPERS.md):
- PREEMPTION: a paged admission that cannot get pages even after LRU
  eviction no longer hard-rejects when a victim slot exists. The
  scheduler preempts the victim (fewest generated tokens, then most
  recently admitted): its prompt + generated sequence goes into the
  radix prefix tree through the EXISTING retire path (the pages
  already hold its KV — insertion is bookkeeping), its page refs are
  released (now evictable), and the request re-queues at the front
  with a resume snapshot (ResumeState: evolved PRNG key, pending spec
  seed token, emitted count). On re-admission the prefix cache hands
  the pages back (match capped at n-1, so only the last token
  recomputes) and decode resumes mid-stream — token streams are
  BITWISE identical preempted vs unpreempted, greedy and sampled,
  spec=K included (tests/test_resilience.py). Hard rejection remains
  only when a single request alone exceeds capacity.
- BACKPRESSURE: `max_queue` bounds the waiting line; submit() returns
  False on overflow and the serving layer replies
  {"busy": true, "retry_after_ms": ...} instead of queueing unboundedly.
- DEADLINES: a Request's optional `deadline_ms` budget (stamped at
  submit) expires queued requests before admission and cancels
  in-flight ones mid-stream with a visible error reason.
- WATCHDOG: `watchdog_s` runs every decode chunk under
  runtime/stress.py::watchdog — a hung chunk surfaces as a clean HANG
  verdict in stats() (and a HangError to the caller) instead of a
  frozen model loop.

Telemetry (runtime/telemetry.py): every counter this module used to
keep in hand-rolled ints lives in a per-scheduler METRICS REGISTRY,
so stats() is one deep, single-point-in-time registry snapshot; the
scheduler additionally records each request's lifecycle
(queued → admitted → prefill_chunk*N → first_token → tokens →
preempt/resume → retired/cancelled/expired) — deriving live `ttft_ms`
and `inter_token_ms` p50/p95/p99 histograms — and, with
``trace=True`` (or TDTPU_TRACE set), a perfetto-loadable poll-loop
timeline: host phase spans, device occupancy (dispatch → `_fetch`
landing), and instants for watchdog fires / preemptions / drains.
Requests may carry an SLO CLASS (`Request.slo`, classes + targets via
``slo_classes=``): latencies then also land in per-class histograms
and partition exactly into slo_goodput/slo_violations — the signal an
SLO-aware admission/preemption policy consumes (ROADMAP item 4). The
coalesced device wait additionally splits per program kind
(``stats()["device_wait_s_by_kind"]``, keyed off mark_dispatch(kind)).
Tracing is host-side only: streams stay BITWISE identical trace-on
vs trace-off with zero new XLA programs (tests/test_telemetry.py,
tests/test_observability.py).

Multi-chip TP (ROADMAP open item 1): ONE scheduler drives a whole
TP=N mesh. The paged pool's page payloads are head-sharded over the
mesh (models/kv_cache.PagedSlotCache TP SHARDING) and the slot
programs run each chip's attention over its local kv-head shard under
shard_map, with the projections on the TP comm backends
(kernels/gemm_allreduce.py "gemm_ar" is the decode-regime pick;
kernels/allgather_gemm.py + gemm_reduce_scatter.py under "dist") —
while EVERYTHING in this module stays host-side and layout-oblivious:
admission, preemption, the radix tree, deadlines and the overlap
pipeline mutate page TABLES and masks, never payloads, so the same
scheduler code serves TP=1 and TP=8 with bitwise-identical streams
(tests/test_tp_serving.py). stats() reports tp_size plus aggregate
AND per-chip tok/s, and lm_head_shards: a dense model's LM head is
split over the vocabulary on the TP axis (models/dense.py), the
logits carry of DecodeSlots with it, so a chip reads a quarter of the
head at TP=4 (tests/test_vocab_parallel_head.py).

Disaggregation (models/disagg.py — DistServe, 2401.09670): chunked
prefill BOUNDS the prefill stall on live streams; `DisaggScheduler`
(a subclass of ContinuousScheduler) REMOVES it — dedicated prefill
workers compute admissions' KV into staging paged pools and stream
the finished pages to this scheduler's decode pool over the
p2p/DCN transfer plane, so decode polls never run a mixed tick at
all. Streams stay bitwise identical disagg vs fused
(tests/test_disagg.py).

Structured generation (models/structured.py — ISSUE 17): two
policy-layer features riding the machinery above unchanged.
PARALLEL SAMPLING: `Request(n=N)` fans out at admission (_fan_out)
into N children; child 0 prefills normally and the moment its slot
arms, _spawn_forks maps its prompt pages into the siblings' tables
(PagedDecodeSlots.fork — refcount+1 on full pages, CoW boundary, the
exact mapping a prefix-cache hit would build, which is why an
overflowed sibling falling back to ordinary admission stays bitwise).
Child k streams under rid (rid, k) at seed seed+k, cancels/preempts/
retires independently, and equals a sequential same-seed request
token-for-token (tests/test_structured.py). GRAMMAR-CONSTRAINED
DECODING: `Request(grammar=GrammarSpec)` collapses the slot's chunk
to 1 (_eff_chunk), threads per-state token masks into the EXISTING
tick programs as logits operands (_mask_chunk/_mask_window — zero new
XLA programs, zero extra host round trips), advances the host
automaton per emitted token (dead end → loud per-request reject,
final state → early finish), and under spec=K turns the automaton's
forced continuation into jump-ahead drafts through the normal verify
path (structured.constrained_draft; `jump_ahead_tokens` counter).
Overlap grammar polls collapse to the sync iteration — the next mask
needs the unlanded token (_grammar_sync_needed).
"""

from __future__ import annotations

import dataclasses
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Tuple

import numpy as np

from triton_dist_tpu.runtime.telemetry import Telemetry, \
    UNTAGGED_PRIORITY, thread_compile_seconds, trace_env_enabled
from triton_dist_tpu.models.structured import NO_FORCED, \
    constrained_draft, window_masks


@dataclasses.dataclass
class ResumeState:
    """Mid-stream snapshot carried by a preempted request: everything
    exact resume needs beyond the (prompt + generated) token sequence
    already folded into Request.ids. The KV itself is NOT snapshotted —
    the radix prefix tree holds the preempted pages (until eviction
    recycles them), and re-admission either maps them back or
    recomputes, bitwise identically either way."""
    key: object = None             # evolved per-slot PRNG key (sampled)
    t0: Optional[int] = None       # pending spec-mode seed token
    emitted: int = 0               # tokens already streamed pre-preempt
    preemptions: int = 1           # times this request was displaced
    gstate: Optional[int] = None   # grammar automaton state (constrained)


@dataclasses.dataclass
class Request:
    """One generation request (the scheduler's admission unit).

    deadline_ms: optional latency budget from submit(); an expired
    request is cancelled with a visible error instead of occupying a
    slot past its usefulness. slo: optional SLO class name
    (runtime/telemetry.py DEFAULT_SLO_CLASSES — "interactive" /
    "batch", or any class the scheduler's `slo_classes` configured):
    lifecycle latencies then land in per-class histograms and the
    request is judged into slo_goodput / slo_violations at its final
    transition. resume: set internally by preemption — callers never
    construct it.

    n > 1 requests PARALLEL SAMPLING (models/structured.py + the
    PagedDecodeSlots.fork KV fork): one prefill, n decode streams with
    seeds seed..seed+n-1, each streaming under rid (rid, k) — bitwise
    identical to n sequential same-seed requests. grammar: an optional
    structured.GrammarSpec; every emitted token is then masked to the
    grammar's legal set inside the tick programs and the stream
    finishes when the grammar completes."""
    rid: object                    # caller's id (any hashable)
    ids: np.ndarray                # prompt token ids [S]
    gen_len: int
    seed: int = 0
    deadline_ms: Optional[float] = None
    slo: Optional[str] = None
    resume: Optional[ResumeState] = None
    n: int = 1                     # parallel samples (KV fork fan-out)
    grammar: object = None         # structured.GrammarSpec (optional)
    # time.monotonic() at which the serving layer's accept() returned
    # the request's connection (the traced lifecycle's first event)
    accepted_at: Optional[float] = None


class _TokenLog:
    """Incrementally grown int32 token log (amortized-doubling numpy
    buffer) backing the per-slot history/token mirrors. Replaces the
    Python-list mirrors whose drafter/retire paths rebuilt a fresh
    array from the whole list every time (O(generated^2) host work over
    a stream's life): appends are amortized O(1) numpy copies and
    ``view()`` is a zero-copy slice the drafter's n-gram scan and the
    radix-tree insert consume directly."""

    __slots__ = ("_buf", "_n")

    def __init__(self, init=None, cap: int = 64):
        self._buf = np.empty((max(int(cap), 8),), np.int32)
        self._n = 0
        if init is not None:
            self.extend(init)

    def __len__(self) -> int:
        return self._n

    def extend(self, toks) -> None:
        toks = np.asarray(toks, np.int32).reshape(-1)
        need = self._n + len(toks)
        if need > len(self._buf):
            buf = np.empty((max(need, 2 * len(self._buf)),), np.int32)
            buf[:self._n] = self._buf[:self._n]
            self._buf = buf
        self._buf[self._n:need] = toks
        self._n = need

    def append(self, t: int) -> None:
        if self._n == len(self._buf):
            buf = np.empty((2 * len(self._buf),), np.int32)
            buf[:self._n] = self._buf[:self._n]
            self._buf = buf
        self._buf[self._n] = t
        self._n += 1

    def pop(self) -> None:
        self._n -= 1

    def view(self) -> np.ndarray:
        """Zero-copy window over the valid extent. Treat as read-only;
        it aliases the growing buffer (``.copy()`` anything that must
        outlive the next append). Note in-place appends only ever
        write PAST the window (growth reallocates), so a view's
        contents are stable even while the log keeps growing."""
        return self._buf[:self._n]

    # sequence protocol + zero-copy numpy conversion: drafters receive
    # the log itself (Drafter.propose takes a Sequence[int]), so both
    # `history[-1]`-style scalar access and np.asarray(history) work
    # without rebuilding a list
    def __getitem__(self, i):
        return self.view()[i]

    def __array__(self, dtype=None):
        v = self.view()
        return v if dtype is None else v.astype(dtype)


@dataclasses.dataclass
class _InFlight:
    """One dispatched-but-unlanded tick (the overlap scheduler's
    pipeline register). ``arrs`` are the device arrays the landing
    fetches in ONE coalesced device_get; ``plan`` is the emission plan
    fixed at dispatch time — (slot, rid, keep) rows for the
    deterministic non-spec modes, (slot, rid) verify rows for spec
    (whose keeps are data-dependent). ``finishing`` (non-spec) lists
    the slots the plan determined will have exhausted their budget
    when this tick lands. ``arm`` (spec mixed ticks) lists prefills
    whose final chunk is in this tick — arming needs the landed
    logits, so it runs at land. rids ride along purely as a guard: the
    drain-before-retire invariant means a slot in an unlanded tick is
    never reassigned, and ``land`` asserts it."""
    kind: str                  # "chunk" | "mixed" | "spec" | "mixed_spec"
    arrs: tuple                     # device arrays to fetch
    plan: list
    finishing: list
    tokens: Optional[np.ndarray] = None    # spec: the verify window
    q_lens: Optional[np.ndarray] = None
    arm: list = dataclasses.field(default_factory=list)


# mark_dispatch kinds -> the attribution buckets stats() reports
# (device_wait_s_by_kind): the chunk scan is the decode tick, and a
# mixed verify is still a mixed tick — the operator-facing question is
# "which program CLASS am I waiting on", not which jit entry point
_DISPATCH_KIND = {"chunk": "decode", "mixed_verify": "mixed",
                  "sp": "sp_combine"}

# _InFlight.kind -> the same buckets, for the overlap land (which must
# charge the LANDED tick's kind, not whatever dispatched since)
_INFLIGHT_KIND = {"chunk": "decode", "mixed": "mixed",
                  "spec": "verify", "mixed_spec": "mixed",
                  "sp": "sp_combine"}


def _merge_out(acc: Dict[object, np.ndarray], rid, toks) -> None:
    """Append landed tokens for one rid to a poll's output dict (a
    drained tick and a freshly landed one can both deliver in the same
    poll — order preserved: drained is older)."""
    toks = np.asarray(toks)
    acc[rid] = (np.concatenate([acc[rid], toks]) if rid in acc
                else toks)


class DecodeSlots:
    """Per-slot decode state: device-side carry (last logits, per-slot
    position, active mask, per-slot PRNG keys) + host-side bookkeeping
    (remaining gen budget, owning request). The device arrays are the
    slot scan's carry — admission and retirement edit rows of them
    between chunks."""

    def __init__(self, engine, batch: int, *, spec: int = 0,
                 drafter=None, telemetry: Optional[Telemetry] = None):
        """spec=K > 0 enables SPECULATIVE DECODING
        (models/spec_decode.py): each step_chunk becomes one
        draft-then-verify iteration — the host `drafter` (default
        NgramDrafter, prompt-lookup over the slot's own history)
        proposes up to K continuation tokens per slot, ONE verify
        forward scores every slot's padded window, and each slot emits
        its seed token plus the accepted draft prefix (1..K+1 tokens
        per forward instead of exactly 1). Greedy streams stay bitwise
        identical to spec=0; sampled streams stay distributionally
        exact (leftover rejection sampling)."""
        import jax
        import jax.numpy as jnp
        self.engine = engine
        self.batch = batch
        # telemetry bundle (runtime/telemetry.py): the registry the
        # lifetime counters below live in, plus the trace hooks the
        # ticks stamp (device occupancy spans, drafter phases). The
        # owning scheduler passes its own; a bare DecodeSlots gets a
        # private trace-off instance.
        self.tele = telemetry if telemetry is not None else Telemetry()
        V = engine.model.config.vocab_size
        self.cache = self._make_cache()
        # carried state starts out placed as a tick returns it: a
        # program is compiled for the placement of its operands, so a
        # first tick fed bare host-made arrays, or logits replicated
        # where the model's head leaves them split over the vocabulary
        # (Engine.logits_sharding), would be compiled again for the
        # second, inside the serving window. _arm_slot keeps the
        # logits there.
        from jax.sharding import NamedSharding, PartitionSpec
        self.logits = jax.device_put(jnp.zeros((batch, V), jnp.float32),
                                     engine.logits_sharding)
        self.pos, self.active = jax.device_put(
            (jnp.zeros((batch,), jnp.int32), jnp.zeros((batch,), bool)),
            NamedSharding(engine.model.mesh, PartitionSpec()))
        self.keys = (None if engine.sampling == "greedy"
                     else jax.random.split(jax.random.key(0), batch))
        # host mirrors (scheduling is host-side; the model never syncs)
        self.remaining = np.zeros((batch,), np.int64)
        self.rids: List[Optional[object]] = [None] * batch
        # full Request per occupant + admission order — the preemption
        # victim policy reads both (fewest generated tokens, then most
        # recently admitted)
        self.reqs: List[Optional[Request]] = [None] * batch
        self.admit_tick = np.zeros((batch,), np.int64)
        self._admit_seq = 0
        # chunked prefill (step_mixed): per-slot PREFILLING state — the
        # full prompt and the resumable offset of the next un-prefilled
        # token. A slot with _pf_ids[b] set is occupied (rids[b] set)
        # but NOT active: it joins decode only when its final chunk
        # lands and _arm_slot runs. prefill_forwarded counts every
        # prompt token actually pushed through a forward (monolithic
        # admissions included) — the scheduler derives its per-poll
        # stall bound from it.
        self._pf_ids: List[Optional[np.ndarray]] = [None] * batch
        self._pf_off = np.zeros((batch,), np.int64)
        self.prefill_forwarded = 0
        # grammar-constrained decoding (models/structured.py): one live
        # host automaton per constrained slot, advanced per emitted
        # token; its allowed-token row rides the tick programs' mask
        # operand (engine.slot_* mask threading) so greedy AND sampled
        # decode select only grammar-legal tokens in-program. on_armed:
        # scheduler hook fired the instant a slot arms
        # (ContinuousScheduler wires its fork fan-out here).
        self._vocab_size = V
        self._grammar: List[Optional[object]] = [None] * batch
        self.on_armed = None
        # slot -> error message for a stream whose automaton hit a dead
        # end (no legal continuation): the scheduler reports the rid's
        # failure loudly instead of emitting garbage
        self.grammar_dead: Dict[int, str] = {}
        # jump-ahead accounting: the verify-window index the
        # GrammarDrafter's forced segment starts at (NO_FORCED = none)
        self._forced_from = np.full((batch,), NO_FORCED, np.int64)
        self._grammar_steps = 0
        greg = self.tele.registry
        self._c_mask_tokens = greg.counter(
            "grammar_mask_tokens",
            "tokens emitted under a grammar mask")
        self._c_jump = greg.counter(
            "jump_ahead_tokens",
            "grammar-forced draft tokens accepted past the base draft")
        self._g_constrained = greg.gauge(
            "constrained_tokens_per_step",
            "grammar-masked tokens emitted per constrained slot-step")
        # overlap scheduling (module docstring): the pipeline register
        # holding one dispatched-but-unlanded tick, and the cumulative
        # time spent BLOCKED on device readbacks (every blocking fetch
        # goes through _fetch) — the scheduler subtracts it from the
        # dispatch-to-dispatch interval to report host_ms_per_poll
        self._inflight: Optional[_InFlight] = None
        self.device_wait_s = 0.0
        # device-time ATTRIBUTION: the same blocking wait split per
        # program kind, keyed off the kind of the most recent
        # mark_dispatch (decode/verify/mixed; "admit" for the
        # out-of-band arming fetches). The disagg plane owns the
        # "prefill"/"transfer" buckets (models/disagg.py) — together
        # the per-kind gauges tell an operator WHICH program class the
        # host actually waits on (stats()["device_wait_s_by_kind"]).
        # PRE-SEEDED with every bucket so the driver's _fetch only
        # ever updates existing keys — cross-thread stats() readers
        # iterate this dict, and a mid-iteration dict RESIZE (unlike a
        # value update) would raise under them.
        self.device_wait_by_kind: Dict[str, float] = {
            "prefill": 0.0, "decode": 0.0, "verify": 0.0,
            "mixed": 0.0, "admit": 0.0, "transfer": 0.0,
            "sp_combine": 0.0, "other": 0.0}
        # MoE-family serving telemetry (ISSUE 13): every tick program
        # of a Qwen3MoE engine appends its routing-load vector
        # [expert_tokens[0..E-1], capacity_dropped]; _fetch pops ONE
        # per landed tick (engine.pop_moe_load — FIFO, so the overlap
        # pipeline never syncs an in-flight tick's stats) and folds it
        # into per-expert `expert_tokens{expert=...}` counters, the
        # `moe_capacity_drops` counter, and the `expert_load_imbalance`
        # (max/mean of cumulative expert load) gauge — the loud half
        # of dropless-or-loud, observable in stats() and /metrics.
        self._moe_family = bool(getattr(engine, "moe_family", False))
        if self._moe_family:
            # engines are shared across schedulers (the process-wide
            # program cache); a prior scheduler that died mid-tick may
            # have left an unlanded stats entry — start aligned
            engine._moe_pending.clear()
            reg = self.tele.registry
            mcfg = engine.model.config
            # a model that holds a stated share of a wider expert set
            # names its experts by their published numbers
            self._c_expert = [
                reg.counter("expert_tokens",
                            "routed entries per expert (compute load)",
                            labels={"expert": str(e)})
                for e in mcfg.expert_ids]
            self._moe_tokens_cum = np.zeros((len(self._c_expert),),
                                            np.int64)
            self._c_pairs_routed = reg.counter(
                "moe_pairs_routed",
                "(token, expert) pairs the router chose, over every "
                "expert it ranks, all layers")
            self._c_pairs_held = reg.counter(
                "moe_pairs_held",
                "those of moe_pairs_routed that landed on experts this "
                "server holds (all of them unless it holds a share)")
            self._c_moe_drops = reg.counter(
                "moe_capacity_drops",
                "routed entries lost to expert capacity (0 under "
                "capacity_factor='dropless')")
            self._g_moe_imb = reg.gauge(
                "expert_load_imbalance",
                "max/mean of cumulative per-expert routed load")
            # what a share's vector holds behind [.., dropped, pairs
            # routed, pairs held]: the model's config names the
            # counters, in the order of its `_zero_load`
            self._c_load_extra = [
                reg.counter(name, text, labels=labels)
                for name, text, labels in getattr(mcfg, "load_counters",
                                                  ())]
        self.spec = int(spec)
        if self.spec:
            from triton_dist_tpu.models.spec_decode import NgramDrafter
            self.drafter = drafter if drafter is not None \
                else NgramDrafter()
            self._vocab = V
            # per-slot token history (prompt + emitted) — the drafter's
            # lookup corpus — and the pending seed token each verify
            # window starts with. _TokenLog: amortized-O(1) appends and
            # a zero-copy view per draft, instead of list mirrors whose
            # per-step conversions cost O(generated^2) over a stream
            self._hist: List[_TokenLog] = [_TokenLog()
                                           for _ in range(batch)]
            self._t0 = np.zeros((batch,), np.int64)
            # accept counters (stats(): spec_accept_rate /
            # tokens_per_step, surfaced through TokenServer). The
            # LIFETIME aggregates (they survive slot reuse) are
            # registry Counters; the per-slot arrays cover the current
            # occupants only (zeroed at admit).
            reg = self.tele.registry
            self._spec_steps = reg.counter(
                "spec_steps", "verify forwards run")
            self._spec_slot_steps = reg.counter(
                "spec_slot_steps", "live (slot, forward) pairs")
            self._spec_emitted = reg.counter(
                "spec_emitted", "tokens kept (incl. seeds)")
            self._spec_drafted_total = reg.counter(
                "spec_drafted", "drafter tokens proposed")
            self._spec_accepted_total = reg.counter(
                "spec_accepted", "drafter tokens accepted")
            self._spec_drafted = np.zeros((batch,), np.int64)
            self._spec_accepted = np.zeros((batch,), np.int64)
            # a drafter that raises (or proposes garbage) must degrade
            # to plain decode, never take down the model loop — the
            # chaos harness (runtime/chaos.py::FlakyDrafter) pins this
            self._drafter_errors = reg.counter("drafter_errors")

    def _make_cache(self):
        """Cache-flavor hook (PagedDecodeSlots swaps in the paged pool)."""
        return self.engine.make_slot_cache(self.batch)

    def _tick_kind(self) -> str:
        """mark_dispatch kind of one plain decode tick ("chunk"; the
        paged subclass reports "sp" over a sequence-parallel pool —
        device_wait_s_by_kind then attributes that tick separately)."""
        return "chunk"

    @property
    def capacity(self) -> int:
        """Admittable prompt+gen budget per slot."""
        return self.cache.k[0].shape[2]

    @property
    def free(self) -> List[int]:
        return [b for b in range(self.batch) if self.rids[b] is None]

    @property
    def occupied(self) -> List[int]:
        return [b for b in range(self.batch) if self.rids[b] is not None]

    @property
    def prefill_slots(self) -> List[int]:
        """Slots mid-chunked-prefill (occupied but not yet armed)."""
        return [b for b in range(self.batch)
                if self._pf_ids[b] is not None]

    @property
    def decode_slots(self) -> List[int]:
        """Occupied slots that are ARMED (emitting tokens) — the rows
        the per-tick emission/retirement bookkeeping covers."""
        return [b for b in self.occupied if self._pf_ids[b] is None]

    def _arm_slot(self, slot: int, req: Request, row_logits, n: int
                  ) -> None:
        """Arm a freshly prefilled slot's rows of the decode carry
        (shared by the contiguous and paged admit paths). A RESUMED
        request (req.resume set — it was preempted mid-stream) restores
        its snapshot instead of restarting: the evolved PRNG key
        replaces jax.random.key(seed) so the sampled chain continues
        exactly where it stopped, and the pending spec seed token is
        restored rather than re-drawn (re-drawing would consume an
        extra key split the unpreempted chain never spent)."""
        import jax
        rs = req.resume
        g = getattr(req, "grammar", None)
        if g is not None:
            gs = g.fresh()
            if rs is not None and rs.gstate is not None:
                # resumed constrained stream: the automaton continues
                # from the preemption snapshot (the generated suffix is
                # already consumed — re-walking it would double-count)
                gs.state = int(rs.gstate)
            self._grammar[slot] = gs
        else:
            self._grammar[slot] = None
        # the row arrives split like the carry (the admission's head
        # pins it), so the eager set keeps the carry's placement; were
        # it ever to return another, the next tick would compile again
        # inside the window: fail here instead, on the host
        self.logits = self.logits.at[slot].set(row_logits)
        assert self.logits.sharding == self.engine.logits_sharding, \
            self.logits.sharding
        self.pos = self.pos.at[slot].set(n)
        self.active = self.active.at[slot].set(True)
        if self.keys is not None:
            self.keys = self.keys.at[slot].set(
                rs.key if rs is not None and rs.key is not None
                else jax.random.key(req.seed))
        self.remaining[slot] = req.gen_len
        self.rids[slot] = req.rid
        self.reqs[slot] = req
        self._admit_seq += 1
        self.admit_tick[slot] = self._admit_seq
        if self.spec:
            # seed the slot's verify chain: history = prompt, pending
            # seed token = what spec=0 would emit first from these
            # logits (greedy argmax on the host; sampled draws through
            # the slot's PRNG chain so the chain stays per-slot)
            self._hist[slot] = _TokenLog(req.ids)
            if rs is not None and rs.t0 is not None:
                self._t0[slot] = int(rs.t0)
            elif self.engine.sampling == "greedy":
                # arming readbacks ride _fetch so their device wait is
                # not misattributed as host time (host_ms_per_poll)
                (row,) = self._fetch((row_logits,), land=False)
                row = np.asarray(row)
                if self._grammar[slot] is not None:
                    # the seed obeys the grammar too (host-side masked
                    # argmax — same selection the tick programs make)
                    row = np.where(self._grammar[slot].allowed_row(),
                                   row, -np.inf)
                self._t0[slot] = int(np.argmax(row))
            else:
                gmask = (self._grammar[slot].allowed_row()
                         if self._grammar[slot] is not None else None)
                t0, k2 = self.engine.spec_seed(row_logits,
                                               self.keys[slot],
                                               mask=gmask)
                self.keys = self.keys.at[slot].set(k2)
                (t0,) = self._fetch((t0,), land=False)
                self._t0[slot] = int(t0)
            self._spec_drafted[slot] = 0
            self._spec_accepted[slot] = 0

    def admit(self, slot: int, req: Request) -> None:
        """Prefill req into `slot` and arm its row of the carry. Only
        the slot's rows change — live slots decode on, unaware."""
        assert self.rids[slot] is None, f"slot {slot} is occupied"
        n = len(req.ids)
        if n + req.gen_len > self.capacity:
            raise ValueError(
                f"request {req.rid!r}: prompt {n} + gen {req.gen_len} "
                f"exceeds slot capacity {self.capacity}")
        row, self.cache = self.engine.prefill_into_slot(
            self.cache, slot, req.ids)
        self.prefill_forwarded += n
        self._arm_slot(slot, req, row, n)

    def admit_chunked(self, slot: int, req: Request) -> None:
        """Chunked admission (prefill_budget mode): validate and park
        the request in a PREFILLING slot — NO forward runs here. The
        prompt prefills chunk by chunk inside subsequent step_mixed
        ticks (each one fused with the live decode step), and the slot
        arms when the final chunk lands. Live slots never wait on a
        monolithic prompt program."""
        assert self.rids[slot] is None, f"slot {slot} is occupied"
        ids = np.asarray(req.ids, np.int32).reshape(-1)
        n = len(ids)
        if n == 0:
            raise ValueError(f"request {req.rid!r}: empty prompt")
        if n + req.gen_len > self.capacity:
            raise ValueError(
                f"request {req.rid!r}: prompt {n} + gen {req.gen_len} "
                f"exceeds slot capacity {self.capacity}")
        self._park_prefilling(slot, req, ids, 0)

    def _park_prefilling(self, slot: int, req: Request, ids: np.ndarray,
                         start: int) -> None:
        """Shared tail of the chunked admissions: register the
        PREFILLING state (pos at the first position to compute —
        `start` is the cached-prefix length on the paged path)."""
        self.pos = self.pos.at[slot].set(start)
        self.active = self.active.at[slot].set(False)
        self.remaining[slot] = 0
        self.rids[slot] = req.rid
        self.reqs[slot] = req
        self._admit_seq += 1
        self.admit_tick[slot] = self._admit_seq
        self._pf_ids[slot] = ids
        self._pf_off[slot] = start

    def emitted(self, slot: int) -> int:
        """Tokens this slot's request has streamed since its ORIGINAL
        admission — resume-aware (a preempted request's pre-preemption
        span rides in resume.emitted). The single source for the
        victim policy, deadline messages, and preemption snapshots."""
        req = self.reqs[slot]
        base = req.resume.emitted if req.resume is not None else 0
        if self._pf_ids[slot] is not None:
            # still prefilling: nothing streamed since this admission
            # (remaining is 0 until the slot arms — without this guard
            # the formula below would claim the whole budget emitted)
            return base
        return base + req.gen_len - int(self.remaining[slot])

    def slo_priority(self, slot: int) -> float:
        """Protection rank of the slot's request: its SLO class's
        configured ``priority`` (runtime/telemetry.py::_SloClass —
        interactive 2.0 / batch 0.0 by default), UNTAGGED_PRIORITY for
        requests with no tag. SLO-aware policies (victim choice,
        prefill-budget splits) displace the LOWEST rank first; when
        every live request shares one rank the priority key is constant
        and those policies degenerate bitwise to the class-blind
        orderings (tests/test_resilience.py asserts this)."""
        req = self.reqs[slot]
        slo = req.slo if req is not None else None
        if slo is None:
            return UNTAGGED_PRIORITY
        cls = self.tele.slo_classes.get(slo)
        return cls.priority if cls is not None else UNTAGGED_PRIORITY

    def emitted_since_admit(self, slot: int) -> int:
        """Tokens streamed since this slot's CURRENT admission (a
        resumed request's pre-preemption span excluded — gen_len is
        already the residual budget). The preemption LIVENESS gate:
        only a slot whose progress is banked in its request (>= 1 token
        folded into ids on preempt) may be displaced, otherwise
        admissions under chunked prefill could displace each other's
        in-progress prefills forever — prefill progress lives in
        EVICTABLE tree pages, so a mid-prefill victim can lose
        everything and the system livelocks (monolithic admissions
        never exposed this: their prefill completes inside the
        admission call, so a resident always reaches emission before
        the next admission phase can displace it)."""
        if self._pf_ids[slot] is not None:
            return 0
        req = self.reqs[slot]
        return req.gen_len - int(self.remaining[slot])

    def retire(self, slot: int) -> None:
        """Free a slot: mask it out of the scan. Its cache row and
        carry rows stay as dead data until the next admit overwrites
        them."""
        self.active = self.active.at[slot].set(False)
        self.remaining[slot] = 0
        self.rids[slot] = None
        self.reqs[slot] = None
        self._pf_ids[slot] = None
        self._pf_off[slot] = 0
        self._grammar[slot] = None
        self.grammar_dead.pop(slot, None)
        self._forced_from[slot] = NO_FORCED
        if self.spec:
            self._hist[slot] = _TokenLog()

    def _fetch(self, arrs: tuple, *, land: bool = True,
               kind: Optional[str] = None) -> tuple:
        """The ONE blocking readback of a tick: a single coalesced
        jax.device_get over every array the tick hands back, timed
        into device_wait_s (the scheduler reports host_ms_per_poll =
        dispatch-to-dispatch interval minus this). Shared by the sync
        steps (fetch right after dispatch) and the overlap land (fetch
        one poll later). land=False for out-of-band readbacks (the
        spec arming seed fetches): they must NOT close the device-
        occupancy span of a tick still in flight — under overlap,
        admission runs between a verify's dispatch and its land.

        kind: explicit attribution bucket for the wait. The overlap
        land passes its in-flight tick's own kind — by land time the
        NEXT tick's dispatch has already overwritten tele.last_kind,
        so deriving it here would misattribute every transition poll.
        None (the sync paths, where the fetch directly follows its
        own mark_dispatch) derives from last_kind; land=False charges
        "admit" (arming fetches block on the admission forward)."""
        import jax
        moe_load = (self.engine.pop_moe_load()
                    if land and self._moe_family else None)
        with self.tele.phase("device_wait") as wait:
            if moe_load is not None:
                # the landed tick's routing-load vector rides the SAME
                # coalesced readback (its outputs are computed by now —
                # this is a d2h copy, not a sync)
                out = jax.device_get(arrs + (moe_load,))
                out, moe_load = out[:-1], out[-1]
            else:
                out = jax.device_get(arrs)
        dt = wait.dt
        self.device_wait_s += dt
        if moe_load is not None:
            self._note_moe_load(moe_load)
        if kind is None:
            kind = (_DISPATCH_KIND.get(self.tele.last_kind,
                                       self.tele.last_kind)
                    if land else "admit")
        # pre-seeded buckets only: stats() readers iterate this dict
        # cross-thread, so _fetch must never RESIZE it
        if kind not in self.device_wait_by_kind:
            kind = "other"
        self.device_wait_by_kind[kind] += dt
        if land:
            # close the device-occupancy span stamped at dispatch
            # (no-op when tracing is off or nothing is pending)
            self.tele.device_land()
        return out

    def _note_moe_load(self, load: np.ndarray) -> None:
        """Fold one landed tick's routing-load vector into the MoE
        serving metrics (driver thread only — the same thread that
        lands ticks)."""
        load = np.asarray(load, np.int64)
        E = len(self._c_expert)
        counts, dropped = load[:E], int(load[E])
        # [.., pairs routed, pairs held] where the model holds a share;
        # a model that holds every expert routes what it holds
        routed, held = (load[E + 1:E + 3] if len(load) > E + 1
                        else (counts.sum() + dropped,) * 2)
        self._c_pairs_routed.inc(int(routed))
        self._c_pairs_held.inc(int(held))
        for c, v in zip(self._c_load_extra, load[E + 3:]):
            c.inc(int(v))
        for e in np.nonzero(counts)[0]:
            self._c_expert[int(e)].inc(int(counts[e]))
        if dropped:
            self._c_moe_drops.inc(dropped)
        self._moe_tokens_cum += counts
        mean = self._moe_tokens_cum.mean()
        self._g_moe_imb.set(
            float(self._moe_tokens_cum.max() / mean) if mean > 0
            else 0.0)

    # ------------------------------------------------------------------
    # grammar-constrained decoding (models/structured.py)
    # ------------------------------------------------------------------

    def _grammar_live(self) -> bool:
        return any(self._grammar[b] is not None
                   for b in self.decode_slots)

    def _mask_chunk(self) -> Optional[np.ndarray]:
        """[B, V] allowed-token mask for one decode tick, or None when
        no armed slot is constrained — None keeps the tick on the
        mask-free jit entry (zero new XLA programs per unconstrained
        poll, the churn-guard contract)."""
        if not self._grammar_live():
            return None
        mask = np.ones((self.batch, self._vocab_size), bool)
        for b in self.decode_slots:
            g = self._grammar[b]
            if g is not None:
                row = g.allowed_row()
                if row.any():
                    mask[b] = row
        return mask

    def _mask_window(self, tokens, q_lens) -> Optional[np.ndarray]:
        """[B, S, V] per-position verify-window mask (spec mode), or
        None when no armed slot is constrained. Position j of a row
        constrains the prediction AFTER tokens[b, :j+1]
        (structured.window_masks has the safety argument for the
        all-True rows past a walk break)."""
        if not self._grammar_live():
            return None
        S = tokens.shape[1]
        mask = np.ones((self.batch, S, self._vocab_size), bool)
        for b in self.decode_slots:
            g = self._grammar[b]
            if g is not None:
                mask[b] = window_masks(g, tokens[b], int(q_lens[b]))
        return mask

    def _grammar_advance(self, b: int, kept) -> None:
        """Advance slot b's automaton over its just-emitted tokens; a
        completed grammar (is_final) finishes the stream early, a dead
        end flags grammar_dead[b] for the scheduler's loud per-request
        error."""
        g = self._grammar[b]
        for t in np.asarray(kept).reshape(-1):
            ok = g.advance(int(t))
            self._c_mask_tokens.inc()
            if not ok or g.is_dead:
                self.grammar_dead[b] = (
                    f"grammar dead end after "
                    f"{self.emitted_since_admit(b)} tokens: no legal "
                    f"continuation from the automaton state")
                self.remaining[b] = 0
                break
            if g.is_final:
                self.remaining[b] = 0
                break
        self._grammar_steps += 1

    def _finish_grammar(self, out: Dict[int, np.ndarray],
                        finished: List[Tuple[int, object]]) -> None:
        """Post-tick automaton advance for the deterministic (non-spec)
        paths: walk each constrained slot's emitted tokens and finish
        the stream when its grammar completes (or dies) — the budget
        zeroing in _grammar_advance is what ends it early."""
        fin = {b for b, _ in finished}
        for b, kept in out.items():
            if self._grammar[b] is None or not len(kept):
                continue
            self._grammar_advance(b, kept)
            if self.remaining[b] == 0 and b not in fin:
                finished.append((b, self.rids[b]))
                fin.add(b)

    def _run_chunk(self, chunk: int):
        """Engine-call hook: DISPATCH one chunk of the slot scan (paged
        variant swaps in paged_slot_chunk). Returns the tick's token
        array still on device — the caller lands it through _fetch
        (sync: immediately; overlap: one poll later)."""
        toks, self.logits, self.cache, self.pos, self.keys = \
            self.engine.slot_chunk(self.logits, self.cache, self.pos,
                                   self.active, chunk=chunk,
                                   keys=self.keys,
                                   mask=self._mask_chunk())
        return toks

    def _record(self, slot: int, toks) -> None:
        """Hook: paged slots record kept tokens for the retire-time
        prefix-tree insert; the contiguous path keeps nothing."""

    def _run_verify(self, tokens, q_lens):
        """Engine-call hook: DISPATCH one spec verify forward (paged
        variant swaps in paged_slot_verify_chunk). Returns device
        (n_emit, t0_next) — landed via _fetch."""
        n_emit, t0n, self.cache, self.pos, self.keys = \
            self.engine.slot_verify_chunk(
                self.cache, self.pos, self.active, tokens, q_lens,
                keys=self.keys, mask=self._mask_window(tokens, q_lens))
        return n_emit, t0n

    def _draft_into(self, tokens: np.ndarray, q_lens: np.ndarray,
                    b: int) -> None:
        """Fill row b of a verify window: the slot's pending seed token
        at column 0 plus up to `spec` drafter proposals (capped at
        remaining - 1, so a slot never writes past its budget). Shared
        by the pure-spec step and the mixed prefill+decode tick."""
        tokens[b, 0] = self._t0[b]
        self._forced_from[b] = NO_FORCED
        kmax = min(self.spec, int(self.remaining[b]) - 1)
        if kmax > 0:
            # append the pending seed for the lookup, then undo — the
            # drafter sees a ZERO-COPY window over the log (no per-step
            # rebuild of the growing history)
            h = self._hist[b]
            h.append(int(self._t0[b]))
            try:
                d = [int(t) for t in
                     self.drafter.propose(h, kmax)][:kmax]
                if any(not 0 <= t < self._vocab for t in d):
                    raise ValueError(f"draft token out of vocab "
                                     f"range [0, {self._vocab})")
            except Exception:
                # a broken drafter degrades to plain decode for
                # this window (the verify still emits the seed
                # token) — it must never take down the model loop
                self._drafter_errors.inc()
                d = []
            finally:
                h.pop()
            g = self._grammar[b]
            if g is not None:
                # grammar stacking: the foreign draft is cut at its
                # first grammar-illegal token, then the window extends
                # with the automaton's FORCED run — jump-ahead: under
                # the mask a forced token is the ONLY legal token at
                # its position, so masked verification accepts the
                # whole deterministic segment in one forward
                d, self._forced_from[b] = constrained_draft(
                    g, int(self._t0[b]), d, kmax)
        else:
            d = []
        tokens[b, 1:1 + len(d)] = d
        q_lens[b] = 1 + len(d)

    def _account_spec(self, b: int, tokens, q_lens, n_emit, t0n,
                      out: Dict[int, np.ndarray],
                      finished: List[Tuple[int, object]]) -> None:
        """Post-verify bookkeeping for one DECODE slot (shared by the
        pure-spec step and the mixed tick): trim the accepted window to
        the remaining budget, thread counters/history, stage the next
        seed token."""
        keep = int(min(self.remaining[b], n_emit[b]))
        g = self._grammar[b]
        if keep and g is not None:
            # walk the REAL automaton over the accepted window: the
            # stream keeps tokens up to a grammar completion (or dead
            # end), and forced tokens kept past the base draft count
            # as jump-ahead wins
            keep2 = 0
            for t in tokens[b, :keep]:
                ok = g.advance(int(t))
                self._c_mask_tokens.inc()
                if not ok or g.is_dead:
                    self.grammar_dead[b] = (
                        "grammar dead end: no legal continuation "
                        "from the automaton state")
                    break
                keep2 += 1
                if g.is_final:
                    break
            self._c_jump.inc(max(0, keep2 - int(self._forced_from[b])))
            self._grammar_steps += 1
            keep = keep2
            if b in self.grammar_dead or g.is_final:
                self.remaining[b] = min(self.remaining[b], keep)
        if keep:
            kept = tokens[b, :keep].copy()
            out[b] = kept
            self.remaining[b] -= keep
            self._hist[b].extend(kept)
            self._record(b, kept)
            self._spec_slot_steps.inc()
            self._spec_emitted.inc(keep)
            self._spec_drafted[b] += int(q_lens[b]) - 1
            self._spec_accepted[b] += keep - 1
            self._spec_drafted_total.inc(int(q_lens[b]) - 1)
            self._spec_accepted_total.inc(keep - 1)
            self._t0[b] = int(t0n[b])
        if self.remaining[b] == 0:
            finished.append((b, self.rids[b]))

    def _step_spec(self) -> Tuple[Dict[int, np.ndarray],
                                  List[Tuple[int, object]]]:
        """One speculative draft-then-verify iteration
        (models/spec_decode.py): the drafter proposes up to `spec`
        continuations of each slot's history + pending seed token
        (capped at remaining - 1, so a slot never writes past its
        budget), ONE verify forward scores every window, and each slot
        keeps its seed plus the accepted draft prefix. The corrected
        token returned by the verify becomes the next window's seed."""
        S = self.spec + 1
        tokens = np.zeros((self.batch, S), np.int32)
        q_lens = np.ones((self.batch,), np.int32)
        with self.tele.phase("drafter"):
            for b in self.decode_slots:
                self._draft_into(tokens, q_lens, b)
        self.tele.mark_dispatch("verify")
        n_emit, t0n = self._fetch(self._run_verify(tokens, q_lens))
        n_emit, t0n = np.asarray(n_emit), np.asarray(t0n)
        self._spec_steps.inc()
        out: Dict[int, np.ndarray] = {}
        finished: List[Tuple[int, object]] = []
        for b in self.decode_slots:
            self._account_spec(b, tokens, q_lens, n_emit, t0n, out,
                               finished)
        return out, finished

    @property
    def stats(self) -> dict:
        """Speculative-decoding counters (empty when spec == 0):
        LIFETIME aggregate accept rate (accepted drafts / proposed
        drafts — survives slot reuse, consistent with spec_emitted /
        spec_steps), tokens emitted per slot per verify forward (1.0 =
        no speculation win, K+1 = every draft accepted), and the
        per-slot counter arrays for the CURRENT occupants. Grammar
        runs additionally report the constrained-decoding counters
        (grammar_mask_tokens / jump_ahead_tokens /
        constrained_tokens_per_step)."""
        out: dict = {}
        if self._grammar_steps:
            per_step = (self._c_mask_tokens.value
                        / self._grammar_steps)
            self._g_constrained.set(round(per_step, 3))
            out.update({
                "grammar_mask_tokens": self._c_mask_tokens.value,
                "jump_ahead_tokens": self._c_jump.value,
                "constrained_tokens_per_step": round(per_step, 3),
            })
        if not self.spec:
            return out
        drafted = self._spec_drafted_total.value
        accepted = self._spec_accepted_total.value
        slot_steps = self._spec_slot_steps.value
        out.update({
            "spec": self.spec,
            "spec_steps": self._spec_steps.value,
            "spec_drafted": drafted,
            "spec_accepted": accepted,
            "spec_emitted": self._spec_emitted.value,
            "spec_accept_rate": (accepted / drafted) if drafted else 0.0,
            "tokens_per_step": (self._spec_emitted.value / slot_steps
                                if slot_steps else 0.0),
            "spec_accepted_per_slot": self._spec_accepted.tolist(),
            "spec_drafted_per_slot": self._spec_drafted.tolist(),
            "drafter_errors": self._drafter_errors.value,
        })
        return out

    def step_chunk(self, chunk: int) -> Tuple[Dict[int, np.ndarray],
                                              List[Tuple[int, object]]]:
        """Run one `chunk`-step slot scan. Returns ({slot: kept tokens
        (trimmed to the slot's remaining budget)}, [(slot, rid) of
        requests that just finished]). Finished slots are NOT retired
        here — the caller streams their tail first, then retires.

        In spec mode (spec=K) one call is one draft-then-verify
        iteration instead of `chunk` single-token steps: each live slot
        emits 1..K+1 tokens per call (seed + accepted drafts)."""
        if self.spec:
            return self._step_spec()
        self.tele.mark_dispatch(self._tick_kind())
        (toks,) = self._fetch((self._run_chunk(chunk),))
        toks = np.asarray(toks)
        plan, finished = self._plan_chunk(chunk)
        out: Dict[int, np.ndarray] = {}
        for b, _, keep in plan:
            out[b] = toks[b, :keep]
            self._record(b, toks[b, :keep])
        self._finish_grammar(out, finished)
        return out, finished

    def _plan_chunk(self, chunk: int, skip=frozenset()
                    ) -> Tuple[list, list]:
        """The deterministic non-spec emission plan of one chunk tick:
        charge each armed slot min(remaining, chunk) and list the
        (slot, rid, keep) rows plus the slots that finish. ONE copy of
        the budget arithmetic, shared by the sync step (which fills in
        the landed token values immediately) and the overlap dispatch
        (which defers them to land()) — the bitwise overlap-on==off
        contract rides on these never drifting."""
        plan, finishing = [], []
        for b in self.decode_slots:
            if b in skip:
                continue
            keep = int(min(self.remaining[b], chunk))
            if keep:
                plan.append((b, self.rids[b], keep))
                self.remaining[b] -= keep
            if self.remaining[b] == 0:
                finishing.append((b, self.rids[b]))
        return plan, finishing

    # ------------------------------------------------------------------
    # chunked prefill: the mixed prefill+decode tick (Sarathi-Serve)
    # ------------------------------------------------------------------

    def _run_mixed(self, tokens, q_lens, pf):
        """Engine hook: DISPATCH one non-spec mixed tick (paged variant
        swaps in paged_slot_mixed_chunk). Updates the carry logits to
        each row's last-valid-window-position logits — a decode row's
        next carry, a final-chunk prefill row's arming logits. Returns
        the device token array (landed via _fetch)."""
        toks, self.logits, self.cache, self.pos, self.keys = \
            self.engine.slot_mixed_chunk(
                self.logits, self.cache, self.pos, self.active, pf,
                tokens, q_lens, keys=self.keys,
                mask=self._mask_chunk())
        return toks

    def _run_mixed_verify(self, tokens, q_lens, pf):
        """Engine hook: DISPATCH one spec-mode mixed tick. The returned
        arming logits replace the (spec-unused) carry so _arm_slot can
        read them per completed prefill. Returns device
        (n_emit, t0_next) — landed via _fetch."""
        n_emit, t0n, self.logits, self.cache, self.pos, self.keys = \
            self.engine.slot_mixed_verify_chunk(
                self.cache, self.pos, self.active, pf, tokens, q_lens,
                keys=self.keys, mask=self._mask_window(tokens, q_lens))
        return n_emit, t0n

    def _pf_record(self, slot: int, toks) -> None:
        """Hook: paged slots extend the VALID-extent token mirror as
        prefill chunks land (retire/preempt mid-prefill must donate
        only tokens whose KV was actually computed)."""

    def _pf_armed(self, slot: int) -> None:
        """Hook: paged slots insert the fully-prefilled prompt into the
        radix tree here (only now is its KV complete — inserting at
        admission, as the monolithic path does, would poison the cache
        with pages the chunks have not written yet)."""

    def step_mixed(self, budget: int) -> Tuple[Dict[int, np.ndarray],
                                               List[Tuple[int, object]]]:
        """One MIXED prefill+decode tick (chunked prefill): ONE forward
        covers every armed decode slot (q_len = 1, or its spec draft
        window) and up to `budget` prompt tokens of in-progress
        prefills, split FIFO by admission order (the oldest admission
        finishes its prefill — and starts streaming — soonest). A
        prefill whose final chunk lands this tick ARMS: its
        last-position logits become the slot's carry and it joins
        decode next tick, exactly as if a monolithic admission had just
        returned. Decode slots emit one token per tick (or their
        accepted spec window) — the most prefill work any live stream
        ever waits on between two of its tokens is `budget` tokens.
        Same return contract as step_chunk."""
        tokens, q_lens, pf, chunks = self._build_mixed_window(budget)
        decode = self.decode_slots
        out: Dict[int, np.ndarray] = {}
        finished: List[Tuple[int, object]] = []
        if self.spec:
            with self.tele.phase("drafter"):
                for b in decode:
                    self._draft_into(tokens, q_lens, b)
            self.tele.mark_dispatch("mixed_verify")
            n_emit, t0n = self._fetch(
                self._run_mixed_verify(tokens, q_lens, pf))
            n_emit, t0n = np.asarray(n_emit), np.asarray(t0n)
            self._spec_steps.inc()
            for b in decode:
                self._account_spec(b, tokens, q_lens, n_emit, t0n, out,
                                   finished)
        else:
            self.tele.mark_dispatch("mixed")
            (toks,) = self._fetch((self._run_mixed(tokens, q_lens, pf),))
            toks = np.asarray(toks)
            plan, finished = self._plan_mixed_decode(decode)
            for b, _, _ in plan:
                kept = toks[b:b + 1].copy()
                out[b] = kept
                self._record(b, kept)
            self._finish_grammar(out, finished)
        # advance the prefills; arm the ones whose final chunk landed
        self._advance_prefills(chunks)
        return out, finished

    def _build_mixed_window(self, budget: int):
        """One mixed tick's window: prefill chunk rows split by SLO
        protection rank (highest class first — an interactive prompt
        absorbs budget before a batch one), FIFO by admission order
        within a rank, under the token budget (q_len 0 = starved, no
        progress). Uniform classes make the rank key constant, so the
        split is the original pure-FIFO one bitwise. ONE copy of the
        split arithmetic, shared by the sync step and the overlap
        dispatch. Returns (tokens, q_lens, pf mask,
        {slot: chunk len})."""
        S = max(int(budget), (self.spec + 1) if self.spec else 1)
        tokens = np.zeros((self.batch, S), np.int32)
        q_lens = np.ones((self.batch,), np.int32)
        pf = np.zeros((self.batch,), bool)
        left = int(budget)
        chunks: Dict[int, int] = {}
        for b in sorted(self.prefill_slots,
                        key=lambda b: (-self.slo_priority(b),
                                       self.admit_tick[b])):
            ids = self._pf_ids[b]
            off = int(self._pf_off[b])
            c = min(len(ids) - off, left, S)
            pf[b] = True
            q_lens[b] = c          # 0 = budget-starved, no progress
            if c:
                tokens[b, :c] = ids[off:off + c]
                chunks[b] = c
            left -= c
        return tokens, q_lens, pf, chunks

    def _plan_mixed_decode(self, decode) -> Tuple[list, list]:
        """Mixed-tick twin of _plan_chunk: each live decode row emits
        exactly one token. Shared by the sync step and the overlap
        dispatch."""
        plan, finishing = [], []
        for b in decode:
            if self.remaining[b] > 0:
                plan.append((b, self.rids[b], 1))
                self.remaining[b] -= 1
            if self.remaining[b] == 0:
                finishing.append((b, self.rids[b]))
        return plan, finishing

    def _advance_prefills(self, chunks: Dict[int, int],
                          arm: Optional[list] = None) -> None:
        """Advance the dispatched prefill chunks' offsets/mirrors and
        handle completions: arm immediately (sync, and the non-spec
        overlap dispatch — arming is sync-free there), or defer by
        appending (slot, req, n) to `arm` (spec overlap: the arming
        logits have not landed yet)."""
        for b, c in chunks.items():
            self.prefill_forwarded += c
            ids = self._pf_ids[b]
            off = int(self._pf_off[b])
            self._pf_record(b, ids[off:off + c])
            self._pf_off[b] = off + c
            self.tele.req_event(self.rids[b], "prefill_chunk", c)
            if self._pf_off[b] == len(ids):
                req = self.reqs[b]
                self._pf_ids[b] = None
                self._pf_off[b] = 0
                if arm is not None:
                    arm.append((b, req, len(ids)))
                else:
                    self._arm_slot(b, req, self.logits[b], len(ids))
                    self._pf_armed(b)
                    if self.on_armed is not None:
                        self.on_armed(b)

    # ------------------------------------------------------------------
    # overlap scheduling: the dispatch/land split (module docstring).
    # begin_* dispatches the SAME engine program its sync step_* twin
    # runs (identical shapes — no new executables) and fixes the
    # emission plan on the host; land() fetches the landed values ONE
    # coalesced device_get later and finishes the bookkeeping that
    # needed them. ContinuousScheduler(overlap=True) drives these.
    # ------------------------------------------------------------------

    def begin_chunk(self, chunk: int, skip=frozenset()) -> None:
        """Dispatch one decode tick WITHOUT reading it back. Non-spec:
        the emission plan is host-deterministic (each armed slot emits
        min(remaining, chunk) tokens), so budgets are charged and
        finishing slots' active masks cleared NOW — the next dispatch
        can run before this tick lands — and only the token VALUES
        (streaming, the paged token mirrors, retirement) wait for
        land(). spec=K delegates to begin_spec (drafts need landed
        history, so the spec pipeline lands within its own poll and
        overlaps the deferred bookkeeping instead). `skip`: slots that
        landed as finished but are not yet retired — no part of this
        tick."""
        assert self._inflight is None, "land() the previous tick first"
        if self.spec:
            self.begin_spec(skip)
            return
        kind = self._tick_kind()
        self.tele.mark_dispatch(kind)
        toks_dev = self._run_chunk(chunk)
        plan, finishing = self._plan_chunk(chunk, skip)
        for b, _ in finishing:
            # masked out of the NEXT tick at dispatch time (sync
            # retires between ticks; the retire itself waits for
            # land — the radix-tree insert needs the token values)
            self.active = self.active.at[b].set(False)
        self._inflight = _InFlight(kind, (toks_dev,), plan, finishing)

    def begin_spec(self, skip=frozenset()) -> None:
        """Dispatch one spec verify tick: drafting reads the LANDED
        history (that is why the spec pipeline cannot dispatch-ahead
        across polls), accept counts are data-dependent, so the whole
        emission plan defers to land()."""
        assert self._inflight is None, "land() the previous tick first"
        S = self.spec + 1
        tokens = np.zeros((self.batch, S), np.int32)
        q_lens = np.ones((self.batch,), np.int32)
        plan = []
        with self.tele.phase("drafter"):
            for b in self.decode_slots:
                if b in skip:
                    continue
                self._draft_into(tokens, q_lens, b)
                plan.append((b, self.rids[b]))
        self.tele.mark_dispatch("verify")
        arrs = self._run_verify(tokens, q_lens)
        self._spec_steps.inc()
        self._inflight = _InFlight("spec", arrs, plan, [],
                                   tokens=tokens, q_lens=q_lens)

    def begin_mixed(self, budget: int, skip=frozenset()) -> None:
        """Dispatch one mixed prefill+decode tick (step_mixed's
        dispatch half). Prefill offsets/mirrors advance NOW (the chunk
        contents are host-known prompt tokens) and a completed
        prefill's arming is sync-free under non-spec (the carry rows
        are device futures); spec arming needs the landed logits so it
        rides the pipeline register to land()."""
        assert self._inflight is None, "land() the previous tick first"
        tokens, q_lens, pf, chunks = self._build_mixed_window(budget)
        decode = [b for b in self.decode_slots if b not in skip]
        if self.spec:
            with self.tele.phase("drafter"):
                for b in decode:
                    self._draft_into(tokens, q_lens, b)
            self.tele.mark_dispatch("mixed_verify")
            arrs = self._run_mixed_verify(tokens, q_lens, pf)
            self._spec_steps.inc()
            inf = _InFlight("mixed_spec", arrs,
                            [(b, self.rids[b]) for b in decode], [],
                            tokens=tokens, q_lens=q_lens)
        else:
            self.tele.mark_dispatch("mixed")
            toks_dev = self._run_mixed(tokens, q_lens, pf)
            plan, finishing = self._plan_mixed_decode(decode)
            for b, _ in finishing:
                self.active = self.active.at[b].set(False)
            inf = _InFlight("mixed", (toks_dev,), plan, finishing)
        # advance the prefills at dispatch time (host-deterministic);
        # spec arming waits for the landed logits (inf.arm)
        self._advance_prefills(chunks, inf.arm if self.spec else None)
        self._inflight = inf

    def land(self) -> Tuple[Dict[int, np.ndarray],
                            List[Tuple[int, object]]]:
        """Fetch the in-flight tick (ONE coalesced device_get) and run
        the value-dependent half of its bookkeeping. Same return
        contract as step_chunk — finished slots are NOT retired here;
        the caller streams their tail first, then retires. No-op
        ({}, []) when nothing is in flight."""
        inf, self._inflight = self._inflight, None
        if inf is None:
            return {}, []
        out: Dict[int, np.ndarray] = {}
        finished: List[Tuple[int, object]] = []
        if inf.kind in ("chunk", "sp", "mixed"):
            (toks,) = self._fetch(inf.arrs,
                                  kind=_INFLIGHT_KIND[inf.kind])
            toks = np.asarray(toks)
            for b, rid, keep in inf.plan:
                assert self.rids[b] == rid, \
                    "slot reassigned under an unlanded tick"
                kept = (toks[b:b + 1] if inf.kind == "mixed"
                        else toks[b, :keep]).copy()
                out[b] = kept
                self._record(b, kept)
            finished = inf.finishing
        else:                                  # "spec" / "mixed_spec"
            n_emit, t0n = self._fetch(inf.arrs,
                                      kind=_INFLIGHT_KIND[inf.kind])
            n_emit, t0n = np.asarray(n_emit), np.asarray(t0n)
            for b, rid in inf.plan:
                assert self.rids[b] == rid, \
                    "slot reassigned under an unlanded tick"
                self._account_spec(b, inf.tokens, inf.q_lens, n_emit,
                                   t0n, out, finished)
            for b, _ in finished:
                # sync clears this inside retire(); the overlap spec
                # pipeline STAGES the retire for the next poll, and the
                # next verify dispatch must not step a finished slot
                self.active = self.active.at[b].set(False)
            for b, req, n in inf.arm:
                self._arm_slot(b, req, self.logits[b], n)
                self._pf_armed(b)
                if self.on_armed is not None:
                    self.on_armed(b)
        return out, finished


class PagedDecodeSlots(DecodeSlots):
    """DecodeSlots over the PAGED pool with the shared-prefix radix
    cache (models/prefix_cache.py): admission consults the radix tree
    for the longest cached prefix, maps those pages READ-ONLY into the
    slot's table rows (refcount +1 each), copy-on-writes the partially
    matched boundary page, and prefills ONLY the uncached suffix
    (engine.admit_slot_paged's prefill-from-offset). Retirement inserts
    the finished sequence (prompt + generated) back into the tree —
    donating the slot's pages — so the NEXT request sharing the prefix
    skips that prefill work. With prefix_cache=False the same programs
    run with a never-matching tree (the bitwise cache-off reference).

    margin: the slot scan keeps stepping a finished slot to its chunk
    boundary; those surplus writes land in the slot's own reserved
    pages (or the trash page past its table rows), so every admission
    reserves capacity for prompt + gen + margin - 1 positions. Pass
    the scheduler's chunk."""

    def __init__(self, engine, batch: int, *, page: int = 16,
                 num_pages: Optional[int] = None,
                 prefix_cache: bool = True, margin: int = 4,
                 spec: int = 0, drafter=None,
                 host_pool_pages: int = 0, fault=None,
                 telemetry: Optional[Telemetry] = None):
        """host_pool_pages > 0 attaches the HOST-RAM KV TIER
        (models/kv_tier.py): LRU eviction demotes unreferenced spans
        to a host pool of that many device-page-sized buffers (d2h
        gather at evict time) instead of dropping them, and a prefix
        match on a host-resident path promotes the span back into
        fresh device pages (h2d install) before the suffix prefill —
        the effective cache grows to num_pages + host_pool_pages while
        streams stay bitwise identical (tests/test_kv_tier.py).
        Meaningful only with prefix_cache=True (a never-matching tree
        never demotes). fault: chaos hook consulted on demotions
        (runtime/chaos.py::FaultInjector.host_demotion)."""
        from triton_dist_tpu.models.prefix_cache import PrefixCache
        # a slot that holds state beside its pages (engine.traits
        # .slot_state) cannot be rebuilt from pages: every option that
        # would is refused here, by the capability it lacks
        if prefix_cache:
            engine.refuse_slot_state(
                "prefix_cache=True", "prefix reuse: a radix-tree hit "
                "maps keys, not the state that followed them; serve "
                "with prefix_cache=False")
        if host_pool_pages:
            engine.refuse_slot_state(
                f"host_pool_pages={host_pool_pages}",
                "host KV tier: demoted pages carry no state")
        if spec:
            engine.refuse_slot_state(
                f"spec={spec}", "speculative verify: a rejected draft "
                "cannot be rolled back out of a recurrent state")
        self.page = page
        self.margin = margin
        self._num_pages = num_pages
        super().__init__(engine, batch, spec=spec, drafter=drafter,
                         telemetry=telemetry)
        # the prefix cache publishes its counters into the SAME
        # registry, so the scheduler's stats() snapshot covers it
        # a SEQUENCE-PARALLEL pool partitions the page-id space per sp
        # shard (kv_cache.PagedSlotCache SP SHARDING): the allocator
        # mirrors that split host-side and rotates fresh pages across
        # shards so a slot's logical tiles interleave chips
        self.prefix = PrefixCache(self.cache.num_pages, page,
                                  enabled=prefix_cache,
                                  host_pool_pages=host_pool_pages,
                                  fault=fault, telemetry=self.tele,
                                  shards=self.cache.sp)
        if host_pool_pages:
            self.prefix.attach_host_tier(self._tier_extract,
                                         self._tier_restore)
        # both sides reserve the same trash page (pool page 0)
        assert self.prefix.pool.trash == self.cache.trash
        # per-slot host mirrors: mapped pages (absolute page
        # order) and the token stream (prompt + kept generated) whose
        # KV those pages hold — the retire-time tree insert. _TokenLog:
        # amortized-O(1) appends + zero-copy views for the tree insert
        # and the preemption snapshot (the list mirrors' per-call
        # rebuilds were O(generated^2) host work over a stream)
        self._pages: List[List[int]] = [[] for _ in range(batch)]
        self._tokens: List[_TokenLog] = [_TokenLog()
                                         for _ in range(batch)]
        # KV fork (parallel sampling — fork() below): per-slot flag
        # backing the forks_active gauge, plus the sharing counters
        self._is_fork = np.zeros((batch,), bool)
        freg = self.tele.registry
        self._c_fork_shared = freg.counter(
            "fork_shared_pages",
            "pages mapped shared (refcount+1) by slot forks")
        self._c_fork_cow = freg.counter(
            "fork_cow_breaks",
            "boundary pages copy-on-written at fork time")
        self._g_forks = freg.gauge(
            "forks_active", "live forked decode slots")
        # what the decode walk moves at a time (kernels/paged_kv.py):
        # fixed by the pool's layout and the mesh, read once
        self._page_copy_bytes = self.cache.page_copy_bytes
        freg.gauge(
            "kv_page_copy_bytes",
            "bytes one K-plane copy of the paged decode walk moves on "
            "a chip: a page's positions for the kv heads the chip holds"
        ).set(self._page_copy_bytes)
        # a cache of several kinds of per-slot state
        # (kv_cache.HybridSlotCache) reports the bytes the live slots
        # hold of each, beside what a uniform cache would hold for them
        self._slot_bytes = (self.cache.slot_bytes()
                            if hasattr(self.cache, "slot_bytes") else None)
        if self._slot_bytes:
            sb = self._slot_bytes
            # a mapped page's bytes go under the cache's own name for
            # them; what a slot holds beside its pages, by kind
            self._page_kind = sb.get("page_kind", "pages")
            self._g_cache_bytes = {
                kind: freg.gauge(
                    "cache_bytes", "bytes the live slots hold, by kind "
                    "of state", labels={"kind": kind})
                for kind in (self._page_kind,) + tuple(
                    k for k in ("index", "window", "state") if k in sb)}
            self._g_uniform_bytes = freg.gauge(
                "cache_uniform_bytes",
                "bytes a uniform cache (every attention layer its own "
                "full-length pool) would hold for the same live slots")

    def _make_cache(self):
        return self.engine.make_paged_slot_cache(
            self.batch, page=self.page, num_pages=self._num_pages)

    def _tick_kind(self) -> str:
        # A SEQUENCE-PARALLEL pool's decode tick runs the split-KV
        # partial + cross-chip LSE combine (layers/tp_attn.py
        # fwd_cached_slots_paged_sp) — attributed as "sp_combine" in
        # device_wait_kind_s so an operator sees what the long-context
        # path actually waits on.
        if getattr(self.engine, "sp_size", 1) > 1:
            return "sp"
        return "chunk"

    # host KV tier copy callbacks (prefix_cache.attach_host_tier):
    # the residency machine calls these from inside evict_until /
    # promote_path — always on the driver thread, with self.cache the
    # live paged pool, so the jitted gather/scatter sequence correctly
    # with the admission/decode programs through data dependence.

    def _tier_extract(self, pages):
        """Demotion d2h: snapshot the span's pages (all layers, every
        head). An int8 pool's payload carries the scale planes too
        ("ks"/"vs") — the d2h/h2d round trip stays bitwise for both
        layouts, the TP-sharded pool included."""
        out = self.engine.extract_pages_host(
            self.cache, np.asarray(pages, np.int32))
        return dict(zip(("k", "v", "ks", "vs"), out))

    def _tier_restore(self, payload, pages) -> None:
        """Promotion h2d: install a snapshot into fresh pages."""
        self.cache = self.engine.restore_pages_host(
            self.cache, np.asarray(pages, np.int32),
            payload["k"], payload["v"],
            payload.get("ks"), payload.get("vs"))

    @property
    def capacity(self) -> int:
        """Admittable prompt+gen budget (table capacity minus the
        chunk-surplus margin)."""
        return self.cache.capacity - self.margin + 1

    @property
    def stats(self) -> dict:
        out = dict(DecodeSlots.stats.fget(self))   # spec + grammar
        nf = int(self._is_fork.sum())
        self._g_forks.set(nf)
        out["forks_active"] = nf
        out["fork_shared_pages"] = self._c_fork_shared.value
        out["fork_cow_breaks"] = self._c_fork_cow.value
        out["kv_page_copy_bytes"] = self._page_copy_bytes
        out.update(self.prefix.stats())
        if self._slot_bytes:
            sb, live = self._slot_bytes, self.occupied
            pages = sum(len(self._pages[b]) for b in live)
            held = {self._page_kind: pages * sb["page"]}
            if "index" in sb:           # a second plane of the same pages
                held["index"] = pages * sb["index"]
            for kind in ("window", "state"):
                if kind in sb:
                    held[kind] = len(live) * sb[kind]
            for kind, v in held.items():
                self._g_cache_bytes[kind].set(v)
            self._g_uniform_bytes.set(
                pages * sb["uniform_page"] + held.get("state", 0))
        return out

    def validate_admission(self, req: Request, tokens: np.ndarray
                           ) -> None:
        """The cheap upfront refusals of a paged admission — ONE copy,
        shared by _reserve_pages and the disagg scheduler's routing
        (models/disagg.py rejects before burning prefill-plane work):
        - empty prompt: the suffix forward needs at least one token
          (and a zero-length prompt would leak the refs _reserve_pages
          retains when the engine refused it);
        - prompt + gen beyond slot capacity;
        - TOTAL footprint beyond the whole pool (shared + fresh pages
          must all coexist): reject upfront with a plain ValueError so
          the scheduler does not preempt every live slot discovering
          it (the cheap denial-of-service a repeated never-fits
          request would otherwise buy)."""
        n = len(tokens)
        if n == 0:
            raise ValueError(f"request {req.rid!r}: empty prompt")
        if n + req.gen_len > self.capacity:
            raise ValueError(
                f"request {req.rid!r}: prompt {n} + gen {req.gen_len} "
                f"exceeds slot capacity {self.capacity}")
        pool = self.prefix.pool
        total = -(-(n + req.gen_len + self.margin - 1) // self.page)
        usable = pool.num_pages - 1
        if total > usable:
            raise ValueError(
                f"request {req.rid!r}: worst-case footprint {total} "
                f"pages exceeds the whole pool ({usable} usable "
                f"pages) — page pool exhausted for this request alone")

    def _reserve_pages(self, req: Request, tokens: np.ndarray):
        """Validation + prefix lookup + page reservation shared by the
        monolithic and CHUNKED paged admissions. Returns (slot_pages,
        m, rows, cow_src, cow_dst, r, boundary) with every ref taken
        (release `boundary` after the device-side CoW ran); raises with
        everything released."""
        n = len(tokens)
        self.validate_admission(req, tokens)
        pool = self.prefix.pool
        # total pages the admitted slot will map (shared + fresh
        # must all coexist in the pool); `need` below is total - full
        total = -(-(n + req.gen_len + self.margin - 1) // self.page)
        m, shared = self.prefix.lookup(tokens)
        full, r = m // self.page, m % self.page
        retained: List[int] = []
        fresh: List[int] = []
        try:
            # pin everything the admission program will read BEFORE
            # eviction can run
            for g in shared[:full]:
                pool.retain(g)
                retained.append(g)
            boundary = shared[full] if r else None
            if boundary is not None:
                pool.retain(boundary)
                retained.append(boundary)
            need = total - full
            if not self.prefix.ensure_pages(need):
                from triton_dist_tpu.models.prefix_cache import \
                    PoolExhausted
                raise PoolExhausted(
                    f"request {req.rid!r}: page pool exhausted "
                    f"({need} fresh pages needed, "
                    f"{pool.available} pages free, nothing evictable)")
            fresh = [pool.alloc_page() for _ in range(need)]
        except ValueError:
            for g in fresh + retained:
                pool.release(g)
            raise
        slot_pages = list(shared[:full]) + fresh
        rows = self._table_row(slot_pages)
        cow_src = boundary if r else self.cache.trash
        cow_dst = fresh[0] if r else self.cache.trash
        return slot_pages, m, rows, cow_src, cow_dst, r, boundary

    def _table_row(self, slot_pages) -> np.ndarray:
        """A slot's row of the page table: its pages in logical order,
        trash past them."""
        row = np.full((self.cache.table.shape[1],), self.cache.trash,
                      np.int32)
        row[:len(slot_pages)] = slot_pages
        return row

    def admit(self, slot: int, req: Request) -> None:
        """Consult the radix tree, map the cached prefix read-only,
        allocate fresh writable pages for the rest (evicting LRU tree
        leaves under pressure), and prefill the uncached suffix."""
        assert self.rids[slot] is None, f"slot {slot} is occupied"
        tokens = np.asarray(req.ids, np.int32).reshape(-1)
        n = len(tokens)
        slot_pages, m, rows, cow_src, cow_dst, r, boundary = \
            self._reserve_pages(req, tokens)
        pool = self.prefix.pool
        row, self.cache = self.engine.admit_slot_paged(
            self.cache, slot, tokens, rows, m, cow_src, cow_dst, r)
        if boundary is not None:
            # only the CoW copy read it; the slot maps its own copy
            pool.release(boundary)
        self.prefill_forwarded += n - m
        self._arm_slot(slot, req, row, n)
        self._pages[slot] = slot_pages
        self._tokens[slot] = _TokenLog(tokens)
        self.prefix.record(n, m)
        # insert the PROMPT pages now (not just at retire): the next
        # admission — even one in the same poll — can already share
        # them. N clients connecting at once with one system prompt is
        # the headline case, and they must not all prefill it.
        self.prefix.insert(tokens, slot_pages[:-(-n // self.page)])

    def admit_chunked(self, slot: int, req: Request) -> None:
        """Chunked paged admission: everything that must happen ONCE —
        prefix lookup, page reservation, table install, boundary-page
        copy-on-write (engine.install_slot_paged) — runs at chunk 0;
        the uncached-suffix forward is left to the step_mixed ticks,
        which scatter their KV through the table just installed. The
        token mirror starts at the CACHED extent (tokens[:m] — their
        pages already hold valid KV) and grows only as chunks land, so
        a retire/preempt/cancel mid-prefill donates exactly what was
        computed; the prompt joins the radix tree at ARMING
        (_pf_armed), not at admission, because until the final chunk
        its fresh pages hold garbage."""
        assert self.rids[slot] is None, f"slot {slot} is occupied"
        tokens = np.asarray(req.ids, np.int32).reshape(-1)
        n = len(tokens)
        slot_pages, m, rows, cow_src, cow_dst, r, boundary = \
            self._reserve_pages(req, tokens)
        self.cache = self.engine.install_slot_paged(
            self.cache, slot, rows, cow_src, cow_dst, r)
        if boundary is not None:
            self.prefix.pool.release(boundary)
        self._pages[slot] = slot_pages
        self._tokens[slot] = _TokenLog(tokens[:m])
        self.prefix.record(n, m)
        self._park_prefilling(slot, req, tokens, m)

    def fork(self, parent: int, slot: int, req: Request) -> None:
        """Clone slot `parent`'s sequence into free slot `slot` — the
        KV fork of parallel sampling (PagedAttention's headline
        physical-sharing case): every FULL page of the parent's current
        sequence maps SHARED (refcount+1; read-only for both sides by
        the write-exclusivity rule tools/tdcheck proves), the partially
        filled boundary page copy-on-writes through the same engine
        path a prefix-cache hit uses, and the fork arms from the
        parent's carry logits with its OWN PRNG key (req.seed) —
        bitwise identical to admitting `req` as a fresh request whose
        prompt fully hits the prefix cache. Fork at ARMING, before the
        parent diverges: both streams then match their sequential
        same-seed replays. After this call the fork is an ordinary
        slot — cancel/preempt/retire/eviction need no special cases
        (retire's tree insert dedups against the parent's pages)."""
        assert self.rids[slot] is None, f"slot {slot} is occupied"
        assert self.rids[parent] is not None \
            and self._pf_ids[parent] is None, \
            f"fork parent {parent} must be an ARMED slot"
        pool = self.prefix.pool
        parent_pages = self._pages[parent]
        # own copy: the parent's log keeps growing under the fork
        tokens = self._tokens[parent].view().copy()
        L = len(tokens)
        self.validate_admission(req, tokens)
        full, r = L // self.page, L % self.page
        total = -(-(L + req.gen_len + self.margin - 1) // self.page)
        retained: List[int] = []
        fresh: List[int] = []
        try:
            # pin the shared prefix (and the boundary the CoW reads)
            # BEFORE eviction can run for the fresh allocations
            for g in parent_pages[:full]:
                pool.retain(g)
                retained.append(g)
            boundary = parent_pages[full] if r else None
            if boundary is not None:
                pool.retain(boundary)
                retained.append(boundary)
            need = total - full
            if not self.prefix.ensure_pages(need):
                from triton_dist_tpu.models.prefix_cache import \
                    PoolExhausted
                raise PoolExhausted(
                    f"request {req.rid!r}: page pool exhausted at "
                    f"fork ({need} fresh pages needed, "
                    f"{pool.available} pages free, nothing evictable)")
            fresh = [pool.alloc_page() for _ in range(need)]
        except ValueError:
            for g in fresh + retained:
                pool.release(g)
            raise
        slot_pages = list(parent_pages[:full]) + fresh
        cow_src = boundary if r else self.cache.trash
        cow_dst = fresh[0] if r else self.cache.trash
        self.cache = self.engine.install_slot_paged(
            self.cache, slot, self._table_row(slot_pages), cow_src,
            cow_dst, r)
        if boundary is not None:
            # only the CoW copy read it; the fork maps its own copy
            pool.release(boundary)
        self._arm_slot(slot, req, self.logits[parent], L)
        self._pages[slot] = slot_pages
        self._tokens[slot] = _TokenLog(tokens)
        self.prefix.record(L, L)      # the whole prefill was skipped
        self._is_fork[slot] = True
        self._c_fork_shared.inc(full)
        if r:
            self._c_fork_cow.inc()

    def preempt(self, slot: int) -> Request:
        """Evict a LIVE slot under pool pressure (vLLM-style recompute
        preemption) and return the request to re-queue. The snapshot is
        tiny because the token sequence IS the state: prompt + kept
        generated tokens become the re-queued request's prompt (its KV
        goes into the radix tree through the normal retire path, so
        re-admission maps the pages back while they survive eviction —
        capped at n-1, only the last token recomputes), gen_len drops
        to the remaining budget, and ResumeState carries what tokens
        cannot encode: the evolved PRNG key (sampled chains continue
        exactly) and the pending spec seed token (already determined,
        never emitted). Works for slots that were themselves resumed —
        ids and the emitted counter just keep accumulating.

        A slot preempted MID-PREFILL (chunked admissions) re-queues its
        ORIGINAL request unchanged — nothing was emitted, so the prompt,
        gen_len, PRNG chain and pending seed are exactly what they were
        at submit (a previously-resumed request keeps its snapshot).
        The computed extent of its prefill still goes into the radix
        tree through retire, so re-admission skips recomputing it while
        the pages survive eviction."""
        req = self.reqs[slot]
        assert req is not None, f"slot {slot} is empty"
        if self._pf_ids[slot] is not None:
            rs = req.resume
            snap = dataclasses.replace(
                rs, preemptions=rs.preemptions + 1) if rs is not None \
                else ResumeState(key=None, t0=None, emitted=0,
                                 preemptions=1)
            self.retire(slot)  # donates the valid prefill extent
            return dataclasses.replace(req, resume=snap)
        # zero-copy: retire() below replaces the log, so the view's
        # buffer is never appended to again — the re-queued request
        # owns it alone
        toks = self._tokens[slot].view()
        remaining = int(self.remaining[slot])
        rs = req.resume
        snap = ResumeState(
            key=self.keys[slot] if self.keys is not None else None,
            t0=int(self._t0[slot]) if self.spec else None,
            emitted=self.emitted(slot),
            preemptions=(rs.preemptions + 1) if rs is not None else 1,
            gstate=(self._grammar[slot].state
                    if self._grammar[slot] is not None else None))
        self.retire(slot)      # tree insert + ref release + trash rows
        return dataclasses.replace(req, ids=toks, gen_len=remaining,
                                   resume=snap)

    def retire(self, slot: int) -> None:
        """Insert the finished sequence back into the tree (the pages
        already hold its KV — insertion is pure bookkeeping), release
        the slot's page refs, and point its table rows at the trash
        page so the masked-out scan rows can never write into a page
        the allocator hands to someone else."""
        if len(self._tokens[slot]):
            npg = -(-len(self._tokens[slot]) // self.page)
            self.prefix.insert(self._tokens[slot].view(),
                               self._pages[slot][:npg])
        for g in self._pages[slot]:
            self.prefix.pool.release(g)
        self.cache = self.engine.retire_slot_paged(self.cache, slot)
        self._pages[slot] = []
        self._tokens[slot] = _TokenLog()
        self._is_fork[slot] = False
        super().retire(slot)

    def _run_chunk(self, chunk: int):
        toks, self.logits, self.cache, self.pos, self.keys = \
            self.engine.paged_slot_chunk(self.logits, self.cache,
                                         self.pos, self.active,
                                         chunk=chunk, keys=self.keys,
                                         mask=self._mask_chunk())
        return toks

    def _run_verify(self, tokens, q_lens):
        n_emit, t0n, self.cache, self.pos, self.keys = \
            self.engine.paged_slot_verify_chunk(
                self.cache, self.pos, self.active, tokens, q_lens,
                keys=self.keys, mask=self._mask_window(tokens, q_lens))
        return n_emit, t0n

    def _run_mixed(self, tokens, q_lens, pf):
        toks, self.logits, self.cache, self.pos, self.keys = \
            self.engine.paged_slot_mixed_chunk(
                self.logits, self.cache, self.pos, self.active, pf,
                tokens, q_lens, keys=self.keys,
                mask=self._mask_chunk())
        return toks

    def _run_mixed_verify(self, tokens, q_lens, pf):
        n_emit, t0n, self.logits, self.cache, self.pos, self.keys = \
            self.engine.paged_slot_mixed_verify_chunk(
                self.cache, self.pos, self.active, pf, tokens, q_lens,
                keys=self.keys, mask=self._mask_window(tokens, q_lens))
        return n_emit, t0n

    def _record(self, slot: int, toks) -> None:
        self._tokens[slot].extend(toks)

    def _pf_record(self, slot: int, toks) -> None:
        # a landed chunk extends the VALID extent — these tokens' KV is
        # now in the slot's pages, so retire/preempt may donate them
        self._tokens[slot].extend(toks)

    def _pf_armed(self, slot: int) -> None:
        # the prompt's KV is complete only now — insert it so the next
        # admission can share it (the monolithic path does this at
        # admit time, where the KV is computed in the same program)
        n = len(self._tokens[slot])
        self.prefix.insert(
            self._tokens[slot].view(),
            self._pages[slot][:-(-n // self.page)])


class ContinuousScheduler:
    """Admit-from-queue / step_chunk / retire loop over DecodeSlots
    (Orca iteration-level scheduling). Single-threaded on the model:
    callers enqueue requests from any thread; one driver thread calls
    poll() (or run()) and owns every jax dispatch."""

    def __init__(self, engine, *, batch: int, chunk: int = 4,
                 paged: bool = False, prefix_cache: bool = True,
                 page: int = 16, num_pages: Optional[int] = None,
                 spec: int = 0, drafter=None,
                 max_queue: Optional[int] = None,
                 watchdog_s: Optional[float] = None,
                 preempt: bool = True, fault=None,
                 prefill_budget: Optional[int] = None,
                 host_pool_pages: int = 0, overlap: bool = False,
                 telemetry: Optional[Telemetry] = None,
                 trace: Optional[bool] = None,
                 slo_classes: Optional[dict] = None):
        """paged=True serves over the paged KV pool with the
        shared-prefix radix cache (models/prefix_cache.py): admissions
        reuse cached prefix pages and skip that prefill work;
        prefix_cache=False keeps the paged pool but never shares (the
        bitwise cache-off reference). num_pages sizes the pool (default:
        worst case, no sharing needed to fit `batch` full slots).

        spec=K > 0 turns each poll's decode step into one speculative
        draft-then-verify iteration (models/spec_decode.py): up to K
        drafter-proposed tokens per slot are scored in ONE forward and
        each slot emits its seed token plus the accepted prefix
        (1..K+1 tokens per forward). Greedy streams are bitwise
        identical to spec=0; sampled streams stay distributionally
        exact. `drafter` defaults to the n-gram/prompt-lookup
        NgramDrafter; stats() then reports spec_accept_rate and
        tokens_per_step.

        Resilience knobs (module docstring has the full story):
        max_queue bounds the waiting line (submit() returns False on
        overflow — backpressure, not an unbounded deque); watchdog_s
        runs each decode chunk under runtime/stress.py::watchdog so a
        hang becomes a HANG verdict in stats() + a HangError, never a
        frozen loop (cost: one short-lived thread per chunk — the
        verdict's price; leave it None when chasing peak loop
        throughput); preempt=False disables KV-pressure preemption
        (pool exhaustion then hard-rejects as before — the differential
        baseline for the bitwise preemption tests); fault is an
        optional chaos hook (runtime/chaos.py::FaultInjector) consulted
        before every admission.

        prefill_budget: CHUNKED PREFILL (Sarathi-Serve, 2403.02310 —
        module docstring). None (default) keeps monolithic admissions;
        an int caps the prompt tokens prefilled per poll across all
        in-progress admissions — while any prefill is in flight, each
        poll runs ONE mixed forward fusing the live decode step with up
        to that many chunk tokens, so the longest stall a live stream
        sees between its tokens is `prefill_budget` prompt tokens
        instead of a whole prompt. Streams are bitwise identical either
        way; tune it to the largest chunk whose added forward latency
        you are willing to put on every live stream's inter-token path
        (decode is bandwidth-bound, so chunks up to a few dozen tokens
        ride the same weight read nearly for free).

        host_pool_pages: HOST-RAM KV TIER (paged path only —
        models/kv_tier.py; PagedDecodeSlots docstring has the design).
        0 (default) keeps single-tier LRU eviction; N > 0 demotes
        evicted spans to a host pool of N device-page-sized buffers
        and promotes them back on a prefix hit, multiplying the
        effective cache to num_pages + N while every stream stays
        bitwise identical. Size it to the host RAM you can pin — tens
        to hundreds of x the HBM pool is the regime it exists for.

        overlap: DISPATCH-AHEAD OVERLAP SCHEDULING (the SGLang
        zero-overhead overlap scheduler — module docstring has the
        pipeline design; TokenServer passes True unless told
        otherwise). False (default here) keeps the synchronous poll:
        dispatch, block on the readback, then do host bookkeeping with
        the device idle. True dispatches tick N+1 before reading back
        tick N (non-spec; spec=K overlaps the deferred retire/admit
        work with its in-poll verify instead), so admissions, the
        radix-tree bookkeeping, drafting and the serving layer's
        socket writes all run while the device computes. Streams are
        BITWISE identical either way (tests/test_overlap.py) — tokens
        just arrive one poll later at stream start, and a freed slot
        re-admits one tick later. Watch stats()["host_ms_per_poll"]:
        when it approaches the device step time, overlap=True is the
        difference between host-bound and device-bound serving.

        telemetry/trace (runtime/telemetry.py — module docstring):
        every scheduler owns a Telemetry bundle; its registry holds
        the counters stats() snapshots and the live `ttft_ms` /
        `inter_token_ms` / `poll_ms` histograms. trace=True
        additionally records per-request event rings and the
        perfetto-loadable poll-loop timeline (host phases + device
        occupancy); the default is the TDTPU_TRACE env convention.
        Tracing is host-side only — streams stay bitwise identical
        and no new XLA program compiles (tests/test_telemetry.py).
        Pass `telemetry` to share or pre-configure the bundle.

        slo_classes: {class_name: {"ttft_target_ms": float,
        "itl_target_ms": float}} — the SLO classes requests may tag at
        submit (Request.slo). None registers the telemetry defaults
        (interactive/batch, runtime/telemetry.DEFAULT_SLO_CLASSES).
        Tagged requests land their latencies in per-class histograms
        and partition into slo_goodput / slo_violations counters at
        their final transition — the measurement substrate ROADMAP
        item 4's admission/preemption policies will consume."""
        if prefill_budget is not None and prefill_budget < 1:
            raise ValueError(f"prefill_budget must be >= 1, got "
                             f"{prefill_budget}")
        if prefill_budget is not None:
            engine.refuse_slot_state(
                f"prefill_budget={prefill_budget}",
                "chunked prefill: the mixed tick carries no state "
                "between a prompt's chunks")
        if telemetry is not None:
            self.tele = telemetry
        else:
            if trace is None:
                trace = trace_env_enabled()
            self.tele = Telemetry(trace=trace)
        self.tele.configure_slo(slo_classes)
        if paged:
            self.slots = PagedDecodeSlots(
                engine, batch, page=page, num_pages=num_pages,
                prefix_cache=prefix_cache, margin=chunk,
                spec=spec, drafter=drafter,
                host_pool_pages=host_pool_pages, fault=fault,
                telemetry=self.tele)
        else:
            self.slots = DecodeSlots(engine, batch, spec=spec,
                                     drafter=drafter,
                                     telemetry=self.tele)
        # KV-fork fan-out (Request.n > 1): siblings of an n>1 parent
        # wait here (keyed by the parent child-0 rid) and fork the
        # parent's pages the instant it arms — the on_armed hook
        # covers the chunked-prefill arming sites; the monolithic
        # admit path calls _spawn_forks directly
        self.slots.on_armed = self._spawn_forks
        self._pending_forks: Dict[object, List[Request]] = {}
        self.chunk = chunk
        self.prefill_budget = prefill_budget
        # the stall bound the chunking buys: the most prefill tokens
        # any single poll pushed through a forward while live streams
        # waited on it (== the longest prompt suffix under monolithic
        # admissions; <= prefill_budget under chunked ones)
        self.max_prefill_tokens_per_poll = 0
        self.max_queue = max_queue
        self.watchdog_s = watchdog_s
        self.preempt = preempt
        self.fault = fault
        self.overlap = bool(overlap)
        # overlap pipeline state: spec-mode finished-but-unretired
        # slots (their retire is deferred to overlap with the next
        # verify), and the carry buffers a mid-phase/between-poll
        # drain lands into (delivered by the next poll)
        self._staged: List[Tuple[int, object]] = []
        self._carry_out: Dict[object, np.ndarray] = {}
        self._carry_done: List[object] = []
        # host_ms_per_poll gauge: dispatch-to-dispatch wall time minus
        # the device wait accumulated in between (DecodeSlots._fetch)
        # and what this thread's dispatches compiled
        self._host_ms_ema: Optional[float] = None
        self._last_mark: Optional[Tuple[float, float, float]] = None
        self._queue: deque = deque()
        # guards _queue/_deadline against cross-thread submit()/cancel()
        # racing the driver thread's poll() (the class contract allows
        # enqueueing from any thread; a bare deque.append was atomic
        # under the GIL, but the deadline stamp + max_queue bound are
        # check-then-act sequences and _expire_deadlines iterates).
        # Reentrant: the overlap drain paths pop finished deadlines
        # from inside already-locked phases.
        self._lock = threading.RLock()
        # rid -> absolute monotonic deadline for requests that carry a
        # deadline_ms budget; preserved across preemptions (keyed by
        # rid, stamped once at first submit)
        self._deadline: Dict[object, float] = {}
        # rid -> rejection reason for requests the slots refused (the
        # serving layer pops these to tell the client WHY it got zero
        # tokens instead of a success-shaped empty stream)
        self.rejected: Dict[object, str] = {}
        # resilience counters, registry-homed (stats() snapshots them;
        # the int-valued properties below keep the old attribute API)
        reg = self.tele.registry
        self._c_preemptions = reg.counter(
            "preemptions", "KV-pressure slot preemptions")
        self._c_deadline_expired = reg.counter(
            "deadline_expired", "requests cancelled past deadline_ms")
        self._c_busy_rejections = reg.counter(
            "busy_rejections", "submits refused at max_queue")
        self._g_host_ms = reg.gauge(
            "host_ms_per_poll",
            "EMA of the wall time from one tick's dispatch to the next "
            "minus the device wait and the compile seconds "
            "(program_compile_s) between them: scheduling, drafting, "
            "admission, the serve loop's intake and socket writes")
        # TP topology + live throughput (multi-chip serving — ROADMAP
        # open item 1): ONE scheduler drives the whole TP mesh, so
        # multi-chip runs must report both aggregate and per-chip
        # numbers. tokens_emitted counts every token delivered to a
        # stream; _busy_s accumulates dispatch-to-dispatch wall time
        # while slots were occupied (idle gaps excluded, same rule as
        # host_ms_per_poll) — stats() derives
        # serving_tok_per_s_aggregate and /tp_size per-chip from them,
        # and the gauges ride the Prometheus exposition.
        self.tp_size = int(
            engine.model.mesh.shape[engine.model.axis])
        reg.gauge("tp_size",
                  "TP mesh size this scheduler drives").set(self.tp_size)
        # whether the vocabulary-parallel head engaged: the chips the
        # LM head's columns (and the logits carry) are split over
        reg.gauge("lm_head_shards",
                  "chips the LM head's vocabulary columns are split "
                  "over (1 = every chip reads the whole head)").set(
            engine.lm_head_shards)
        # sequence-parallel topology (long-context serving): the sp
        # mesh size the paged pool's page-id space shards over —
        # per-chip KV reads and attention FLOPs drop to ~1/sp_size and
        # max context scales with it (1 = no sp)
        self.sp_size = int(getattr(engine, "sp_size", 1))
        reg.gauge("sp_size",
                  "sp mesh size the paged pool shards over").set(
            self.sp_size)
        self._c_tokens = reg.counter(
            "tokens_emitted", "tokens delivered to client streams")
        # how often dispatch-ahead engages (module docstring): ticks
        # that ran with host work under them, and the collapses
        self._c_ahead = reg.counter(
            "ticks_dispatched_ahead",
            "ticks dispatched before the previous tick's retires, the "
            "wire writes and the next intake (overlap, spec=0)")
        self._c_drains = reg.counter(
            "pipeline_drains",
            "overlap pipeline collapses: preemption, cancel, an "
            "in-flight deadline, a PoolExhausted admission, a grammar "
            "tick")
        self._busy_s = 0.0
        self._hang: Optional[str] = None

    # registry-homed counters behind the old int attribute API (tests
    # and bench read these as plain ints)
    @property
    def preemptions(self) -> int:
        return self._c_preemptions.value

    @property
    def deadline_expired(self) -> int:
        return self._c_deadline_expired.value

    @property
    def busy_rejections(self) -> int:
        return self._c_busy_rejections.value

    def dump_trace(self, path: str) -> None:
        """Write the telemetry export (poll timeline + request traces
        + metrics snapshot) as perfetto-loadable JSON — the
        TDTPU_TRACE dump; summarize with tools/trace_view.py."""
        self.tele.dump(path)

    def submit(self, req: Request) -> bool:
        """Enqueue a request. Returns False — WITHOUT queueing — when
        the waiting line is at max_queue: the caller owes the client a
        busy/retry-later reply instead of unbounded buffering. Internal
        re-queues (preemption) bypass the bound — a preempted request
        was already admitted once and must never be dropped.
        Thread-safe: any thread may submit while the driver polls."""
        if req.n > 1:
            self.slots.engine.refuse_slot_state(
                f"request {req.rid!r} n={req.n}",
                "KV fork: a fork shares pages, and state is not shared")
        with self._lock:
            if self.max_queue is not None \
                    and len(self._queue) >= self.max_queue:
                self._c_busy_rejections.inc()
                return False
            if req.deadline_ms is not None \
                    and req.rid not in self._deadline:
                self._deadline[req.rid] = time.monotonic() \
                    + req.deadline_ms / 1e3
            # lifecycle stamp INSIDE the lock: the driver may admit
            # (and emit for) this request the instant it is visible in
            # the queue, and emit/retire need the record to exist
            self.tele.queued(req.rid, slo=req.slo,
                             accepted_at=req.accepted_at)
            self._queue.append(req)
        return True

    @property
    def queue_depth(self) -> int:
        return len(self._queue)

    def cancel(self, rid) -> bool:
        """Drop a request mid-flight (cancel-on-disconnect): a queued
        request is removed; an in-flight one retires NOW — its slot,
        carry rows and (paged) pages free immediately instead of
        decoding to gen_len with the tokens falling on the floor. The
        tokens generated so far are still valid, so a paged retire
        inserts them into the prefix tree as usual. Returns False for
        an unknown/finished rid.

        Threading contract: removing a QUEUED request is safe from any
        thread (it shares the submit lock). Cancelling an IN-FLIGHT
        slot mutates the decode carry and releases pages, so it must
        run on the driver thread or be serialized with poll() by the
        caller — racing a live chunk could retire a slot the driver
        just re-armed and free pages a masked row still writes.
        TokenServer does exactly this: cancel and poll both run under
        its own lock."""
        with self._lock:
            for i, r in enumerate(self._queue):
                if r.rid == rid:
                    del self._queue[i]
                    self._deadline.pop(rid, None)
                    self.tele.retire(rid, "cancelled")
                    # a cancelled fork parent orphans its waiting
                    # siblings: they queue as ordinary admissions
                    # (prefix cache keeps their streams identical)
                    for kid in self._pending_forks.pop(rid, ()):
                        self._queue.append(kid)
                    return True
            # a fork sibling still waiting on its parent's arming
            for kids in self._pending_forks.values():
                for i, kid in enumerate(kids):
                    if kid.rid == rid:
                        del kids[i]
                        self._deadline.pop(rid, None)
                        self.tele.retire(rid, "cancelled")
                        return True
        if self.overlap and not self._pipeline_idle() \
                and any(self.slots.rids[b] == rid
                        for b in self.slots.occupied):
            # the rid's slot may be in the unlanded tick: land + retire
            # first (other streams' landed tokens go to the carry
            # buffers, delivered by the next poll), then cancel on
            # consistent state — the rid may turn out to have finished
            self._drain(self._carry_out, self._carry_done)
        for b in self.slots.occupied:
            if self.slots.rids[b] == rid:
                self.slots.retire(b)
                with self._lock:
                    self._deadline.pop(rid, None)
                    # parent cancelled mid-prefill: its waiting
                    # siblings re-queue as ordinary admissions
                    for kid in self._pending_forks.pop(rid, ()):
                        self._queue.append(kid)
                self.tele.retire(rid, "cancelled")
                return True
        return False

    def stats(self) -> dict:
        """Serving counters: prefix-cache hit/skip (paged path),
        speculative-decoding accept counters (spec=K mode —
        spec_accept_rate, tokens_per_step), the resilience counters
        (queue_depth, preemptions, deadline_expired, busy_rejections,
        plus a "hang" verdict string once a watchdogged chunk has
        missed its deadline), and the live latency histograms
        (`ttft_ms` / `inter_token_ms` / `request_latency_ms` /
        `poll_ms` as {count, sum, mean, p50, p95, p99} dicts).

        The result is a DEEP, single-point-in-time snapshot of the
        metrics registry (runtime/telemetry.py) taken under the
        scheduler and registry locks: every container is freshly
        allocated, so cross-thread readers can iterate/serialize it
        while the driver keeps polling — the shallow-copy race the
        old three hand-maintained dicts carried is structurally
        gone (tests/test_telemetry.py hammers this)."""
        reg = self.tele.registry
        with self._lock, reg.lock:
            # point-in-time gauges refreshed first (prefix/host-tier
            # gauges refresh inside slots.stats), then ONE registry
            # snapshot, then the config echoes and derived rates
            reg.gauge("queue_depth").set(len(self._queue))
            reg.gauge("prefill_tokens_forwarded").set(
                self.slots.prefill_forwarded)
            reg.gauge("max_prefill_tokens_per_poll").set(
                self.max_prefill_tokens_per_poll)
            reg.gauge("prefills_in_progress").set(
                len(self.slots.prefill_slots))
            reg.gauge("device_wait_s").set(self.slots.device_wait_s)
            # device-time attribution: the coalesced wait split per
            # program kind (decode/verify/mixed/admit — the fused
            # planes; the disagg subclass owns prefill/transfer). A
            # DISTINCT base name from the device_wait_s total, so
            # summing the labeled series never double-counts it.
            by_kind = {k: round(v, 4) for k, v in
                       self.slots.device_wait_by_kind.items()}
            for k in ("prefill", "decode", "verify", "mixed",
                      "sp_combine", "admit", "transfer"):
                reg.gauge("device_wait_kind_s",
                          labels={"kind": k}).set(by_kind.get(k, 0.0))
            # live throughput, aggregate AND per-chip (one scheduler
            # drives the whole TP mesh — the per-chip number is the
            # one comparable across topologies)
            reg.gauge("tp_size").set(self.tp_size)
            agg = (self._c_tokens.value / self._busy_s
                   if self._busy_s > 0 else 0.0)
            nchips = self.tp_size * self.sp_size
            reg.gauge("serving_tok_per_s_aggregate",
                      "tokens/s across the whole mesh while "
                      "serving").set(round(agg, 3))
            reg.gauge("serving_tok_per_s_per_chip",
                      "aggregate tok/s / mesh size").set(
                round(agg / nchips, 3))
            slots_stats = dict(getattr(self.slots, "stats", {}) or {})
            out = reg.snapshot()
            out.update(slots_stats)
            out.update({
                "tp_size": self.tp_size,
                "sp_size": self.sp_size,
                "tokens_emitted": self._c_tokens.value,
                "serving_tok_per_s_aggregate": round(agg, 3),
                "serving_tok_per_s_per_chip":
                    round(agg / nchips, 3),
                "queue_depth": len(self._queue),
                "preemptions": self._c_preemptions.value,
                "deadline_expired": self._c_deadline_expired.value,
                "busy_rejections": self._c_busy_rejections.value,
                "prefill_budget": self.prefill_budget,
                "prefill_tokens_forwarded":
                    self.slots.prefill_forwarded,
                "max_prefill_tokens_per_poll":
                    self.max_prefill_tokens_per_poll,
                "prefills_in_progress": len(self.slots.prefill_slots),
                # host time per poll with device wait subtracted
                # (EMA): the number overlap=True exists to hide
                # behind the device
                "overlap": self.overlap,
                "host_ms_per_poll": (0.0 if self._host_ms_ema is None
                                     else round(self._host_ms_ema, 3)),
                "device_wait_s": round(self.slots.device_wait_s, 4),
                "device_wait_s_by_kind": by_kind,
                "host_phase_s": self.tele.phase_seconds(),
                "slo_classes": {
                    name: {"ttft_target_ms": c.ttft_target_ms,
                           "itl_target_ms": c.itl_target_ms,
                           "priority": c.priority}
                    for name, c in self.tele.slo_classes.items()},
            })
            if self._hang is not None:
                out["hang"] = self._hang
        return out

    def _mark_dispatch(self) -> None:
        """Stamp a device-step dispatch: host_ms_per_poll is the time
        since the previous stamp minus the device wait accrued in
        between (DecodeSlots._fetch) and the seconds jax compiled for
        this thread's dispatches (a shape the warm-up did not meet:
        program_compile_s has them) — i.e. what the HOST spent
        scheduling, drafting, streaming and admitting per poll,
        whether or not the device was busy under it."""
        now = time.monotonic()
        wait = self.slots.device_wait_s
        comp = thread_compile_seconds()
        if self._last_mark is not None:
            t0, w0, c0 = self._last_mark
            host_ms = max(0.0, ((now - t0) - (wait - w0)
                                - (comp - c0)) * 1e3)
            self._host_ms_ema = host_ms if self._host_ms_ema is None \
                else 0.8 * self._host_ms_ema + 0.2 * host_ms
            self._g_host_ms.set(self._host_ms_ema)   # registry mirror
            # serving time base for the live tok/s gauges (stats()):
            # dispatch-to-dispatch wall while occupied, idle excluded
            self._busy_s += now - t0
        self._last_mark = (now, wait, comp)

    @property
    def idle(self) -> bool:
        return (not self._queue and not self.slots.occupied
                and not self._carry_out and not self._carry_done)

    def _eff_chunk(self) -> int:
        """Decode chunk for the next tick: a grammar mask is a
        per-step scan constant (engine.slot_chunk contract), so any
        live constrained slot drops the tick to single-step;
        unconstrained polls keep the configured chunk."""
        slots = self.slots
        if any(slots._grammar[b] is not None
               for b in slots.decode_slots):
            return 1
        return self.chunk

    def _grammar_sync_needed(self) -> bool:
        """overlap=True cannot dispatch-ahead a spec=0 grammar tick:
        the next tick's mask depends on the token the unlanded tick
        emits. spec=K grammar polls land in-poll already (begin_spec)
        and stay on the overlap path."""
        if self.slots.spec:
            return False
        slots = self.slots
        if any(slots.reqs[b] is not None
               and getattr(slots.reqs[b], "grammar", None) is not None
               for b in range(slots.batch)):
            return True
        with self._lock:
            return any(getattr(r, "grammar", None) is not None
                       for r in self._queue)

    def _fan_out(self, req: Request) -> Request:
        """Validate the structured-generation fields of the admission
        at the queue head and split an n>1 request into n same-prompt
        children: child 0 prefills normally; children 1..n-1 wait in
        _pending_forks and FORK the armed slot's pages (one prefill, n
        decode streams). Child k streams under rid (rid, k) with seed
        seed+k — bitwise identical to n sequential same-seed requests
        (the fork maps exactly the pages a sequential admission's
        prefix-cache hit would). Raises ValueError (the caller's
        reject path) on invalid n or an unsupported combination."""
        n = int(getattr(req, "n", 1) or 1)
        g = getattr(req, "grammar", None)
        if n < 1:
            raise ValueError(
                f"request {req.rid!r}: n must be >= 1, got {n}")
        if g is not None:
            if g.vocab_size != self.slots._vocab_size:
                raise ValueError(
                    f"request {req.rid!r}: grammar compiled for vocab "
                    f"{g.vocab_size}, engine vocab is "
                    f"{self.slots._vocab_size}")
        if n == 1:
            return req
        if not hasattr(self.slots, "fork"):
            raise ValueError(
                f"request {req.rid!r}: n={n} parallel sampling needs "
                f"the paged KV pool (ContinuousScheduler(paged=True)) "
                f"— contiguous slots cannot share prefix pages")
        if n > self.slots.batch:
            raise ValueError(
                f"request {req.rid!r}: n={n} exceeds the slot batch "
                f"{self.slots.batch}")
        kids = [dataclasses.replace(req, rid=(req.rid, k),
                                    seed=req.seed + k, n=1)
                for k in range(n)]
        dl = self._deadline.pop(req.rid, None)
        for kid in kids:
            self.tele.queued(kid.rid, slo=kid.slo)
            if dl is not None:
                self._deadline[kid.rid] = dl
        # the parent rid's lifecycle record closes here — the client
        # streams under the (rid, k) children from now on
        self.tele.retire(req.rid, "forked")
        self._queue[0] = kids[0]
        self._pending_forks[kids[0].rid] = kids[1:]
        return kids[0]

    def _spawn_forks(self, slot: int) -> None:
        """on_armed hook: the instant an n>1 parent (child 0) arms,
        fork its pages into free slots for the waiting siblings. A
        sibling that cannot fork NOW (no free slot / pool exhausted)
        falls back to the FRONT of the queue as an ordinary admission
        — the parent's prompt pages are in the prefix tree, so it
        still skips the shared prefill (same streams, degraded
        sharing)."""
        rid = self.slots.rids[slot]
        kids = self._pending_forks.pop(rid, None)
        if not kids:
            return
        from triton_dist_tpu.models.prefix_cache import PoolExhausted
        overflow: List[Request] = []
        for i, kid in enumerate(kids):
            free = self.slots.free
            if not free:
                overflow = kids[i:]
                break
            try:
                self.slots.fork(slot, free[0], kid)
                self.tele.req_event(kid.rid, "admitted", free[0])
            except (PoolExhausted, ValueError):
                overflow = kids[i:]
                break
        if overflow:
            with self._lock:
                for kid in reversed(overflow):
                    self._queue.appendleft(kid)

    def _reject(self, rid, reason: str,
                status: str = "rejected") -> None:
        import sys
        print(f"[scheduler] rejected request {rid!r}: {reason}",
              file=sys.stderr)
        self.rejected[rid] = reason
        while len(self.rejected) > 1024:
            # bound the side channel: callers that never read
            # reasons (run()/bench loops) must not leak — drop
            # oldest first (dict preserves insertion order)
            self.rejected.pop(next(iter(self.rejected)))
        self._deadline.pop(rid, None)
        self.tele.retire(rid, status)

    def _expire_deadlines(self, done: List[object]) -> None:
        """Cancel everything past its deadline_ms budget: queued
        requests are dropped before wasting an admission; in-flight
        slots retire NOW (a paged retire still donates the partial
        sequence to the prefix tree — the tokens are valid), with a
        visible reason the serving layer reports as an error."""
        if not self._deadline:
            return
        now = time.monotonic()
        expired = {rid for rid, dl in self._deadline.items()
                   if now >= dl}
        if not expired:
            return
        if any(r.rid in expired for r in self._queue):
            keep: deque = deque()
            for r in self._queue:
                if r.rid in expired:
                    self._c_deadline_expired.inc()
                    if r.resume is not None:
                        # preempted mid-stream, expired while waiting
                        # to resume: the client DID receive tokens —
                        # say so, like the in-flight branch
                        reason = (f"deadline_ms={r.deadline_ms:g} "
                                  f"exceeded after {r.resume.emitted} "
                                  f"tokens (preempted, awaiting resume)")
                    else:
                        reason = (f"deadline_ms={r.deadline_ms:g} "
                                  f"expired before admission")
                    self._reject(r.rid, reason, status="expired")
                    done.append(r.rid)
                    # siblings of an expired fork parent re-queue as
                    # ordinary admissions (their own copied deadlines
                    # expire them on the next pass)
                    for kid in self._pending_forks.pop(r.rid, ()):
                        keep.append(kid)
                else:
                    keep.append(r)
            self._queue = keep
        for b in list(self.slots.occupied):
            rid = self.slots.rids[b]
            if rid in expired:
                req = self.slots.reqs[b]
                emitted = self.slots.emitted(b)
                self.slots.retire(b)
                self._c_deadline_expired.inc()
                self._reject(rid, f"deadline_ms={req.deadline_ms:g} "
                                  f"exceeded after {emitted} tokens",
                             status="expired")
                done.append(rid)
                for kid in self._pending_forks.pop(rid, ()):
                    self._queue.append(kid)

    def _eligible_victims(self) -> List[int]:
        """Slots that may be preempted: they emitted at least one token
        since their current admission, so displacement banks real
        progress in the re-queued request (see
        DecodeSlots.emitted_since_admit — the liveness gate that keeps
        chunked-prefill admissions from thrashing each other's
        in-progress, eviction-fragile prefills forever)."""
        slots = self.slots
        return [b for b in slots.occupied
                if slots.emitted_since_admit(b) > 0]

    def _pick_victim(self, candidates: List[int]) -> int:
        """Preemption victim policy: lowest SLO protection rank first
        (a "batch" stream is displaced before an "interactive" one —
        DecodeSlots.slo_priority; uniform classes collapse the leading
        key and the choice is the class-blind one bitwise), then fewest
        generated tokens (least recompute thrown away — the
        long-running streams finish), ties to the most recently
        admitted (it displaced the least)."""
        slots = self.slots
        return min(candidates,
                   key=lambda b: (slots.slo_priority(b),
                                  slots.emitted(b),
                                  -int(slots.admit_tick[b])))

    def _preempt_for(self, rid, preempted_now: set, reason: str, *,
                     drop, requeue_at: int = 1) -> bool:
        """The preempt-or-wait ladder of one PoolExhausted admission —
        ONE copy, shared by the fused _admit and the disagg
        scheduler's install/resume paths (models/disagg.py). Returns
        False = stop admitting this poll (an in-flight resident may
        become eligible, or this rid was already preempted-for once);
        True = retry (a victim was freed, or preemption is off and the
        request was hard-rejected via `drop(reason)`). requeue_at: the
        victim's queue position — 1 when the displacer is _queue[0]
        (the victim must NOT jump ahead of the request it was evicted
        for, or the two ping-pong the slot while the displacer
        starves), 0 when the displacer is not in the queue (the disagg
        transfer queue installs ahead of the queue anyway)."""
        can_preempt = (self.preempt and self.slots.occupied
                       and hasattr(self.slots, "preempt"))
        if not can_preempt:
            drop(reason)
            return True
        if rid in preempted_now:
            return False
        victims = self._eligible_victims()
        if not victims:
            # in-flight slots exist but none has banked progress yet
            # (fresh admissions / mid-chunked-prefill): WAIT a poll
            # instead of displacing them — the step advances them to
            # eligibility (or retirement), where preempting now could
            # throw away eviction-fragile prefill work forever
            return False
        victim = self.slots.preempt(self._pick_victim(victims))
        self._c_preemptions.inc()
        self.tele.req_event(victim.rid, "preempt")
        self.tele.instant("preempt", str(victim.rid))
        preempted_now.add(victim.rid)
        self._queue.insert(min(requeue_at, len(self._queue)), victim)
        return True

    def _pipeline_idle(self) -> bool:
        """No dispatched-but-unlanded tick and no staged retires — the
        host mirrors equal what sync mode would show at this poll
        boundary, so preempt/cancel/deadline paths may mutate slots."""
        return self.slots._inflight is None and not self._staged

    def _drain(self, out_acc: Dict[object, np.ndarray],
               done: List[object]) -> None:
        """Collapse the overlap pipeline to the sync post-poll state:
        land the in-flight tick (its tokens/done merge into the given
        accumulators) and retire every finished-but-unretired slot —
        staged spec finishers first, then the just-landed ones. The
        drain-before-mutate rule (module docstring) routes every
        preemption, cancel and in-flight deadline expiry through
        here. The land runs watchdogged (_land_watchdog) — a drain's
        readback can hang exactly like a poll's."""
        self.tele.instant("drain")
        self._c_drains.inc()
        out, finished = self._land_watchdog()
        rid_of = self.slots.rids
        for b, t in out.items():
            _merge_out(out_acc, rid_of[b], t)
        with self._lock:
            for b, rid in finished:
                self._deadline.pop(rid, None)
                done.append(rid)
        for b, rid in self._staged + finished:
            if self.slots.rids[b] == rid:
                self.slots.retire(b)
        self._staged = []

    def _expire_overlap(self, out_acc: Dict[object, np.ndarray],
                        done: List[object]) -> None:
        """_expire_deadlines behind the drain rule: an expired rid that
        occupies a slot may be in the unlanded tick (its mirrors lag by
        one tick), so the pipeline drains first. Queued-only expiries
        never need the drain."""
        if self._deadline and not self._pipeline_idle():
            now = time.monotonic()
            live = {r for r in self.slots.rids if r is not None}
            if any(now >= dl and rid in live
                   for rid, dl in self._deadline.items()):
                self._drain(out_acc, done)
        self._expire_deadlines(done)

    def _admit(self, done: List[object],
               out_acc: Optional[Dict[object, np.ndarray]] = None
               ) -> None:
        """Refill free slots from the waiting line. A PoolExhausted
        admission PREEMPTS a victim and retries instead of rejecting,
        whenever an ELIGIBLE victim exists — one that emitted at least
        a token since its current admission (_eligible_victims: the
        liveness gate; a fresh or mid-chunked-prefill resident may not
        be displaced, the admission waits a poll instead). The victim's
        request re-queues right behind the admission that displaced it,
        its pages now evictable through the prefix tree. Hard rejection
        remains only when every victim is gone and the pool still
        cannot fit the request (it alone exceeds capacity). A request
        preempted within THIS poll that immediately fails re-admission
        waits for the next chunk instead of thrashing the slots it just
        lost."""
        from triton_dist_tpu.models.prefix_cache import PoolExhausted
        preempted_now: set = set()
        while self._queue:
            free = self.slots.free
            if not free:
                return
            req = self._queue[0]
            try:
                if self.fault is not None:
                    self.fault.admission(req)
                req = self._fan_out(req)
                if self.prefill_budget is not None:
                    self.slots.admit_chunked(free[0], req)
                else:
                    self.slots.admit(free[0], req)
                self._queue.popleft()
                self.tele.req_event(
                    req.rid,
                    "resume" if req.resume is not None else "admitted",
                    free[0])
                if self.prefill_budget is None:
                    # monolithic arming happened inside admit (no
                    # on_armed site): fan the waiting siblings out now
                    self._spawn_forks(free[0])
            except PoolExhausted as e:
                if self.overlap and not self._pipeline_idle():
                    # land + retire first: pages still held by the
                    # in-flight tick's finishers may satisfy the
                    # admission without preempting anyone — and
                    # preempt() itself must only run on landed state
                    self._drain(self._carry_out if out_acc is None
                                else out_acc, done)
                    continue

                def _drop(reason, req=req):
                    self._queue.popleft()
                    self._reject(req.rid, reason)
                    done.append(req.rid)
                    # a hard-rejected fork parent orphans its waiting
                    # siblings — reject them with the same reason
                    for kid in self._pending_forks.pop(req.rid, ()):
                        self._reject(kid.rid, reason)
                        done.append(kid.rid)

                if not self._preempt_for(req.rid, preempted_now,
                                         str(e), drop=_drop):
                    return
            except ValueError as e:
                self._queue.popleft()
                self._reject(req.rid, str(e))
                done.append(req.rid)
                for kid in self._pending_forks.pop(req.rid, ()):
                    self._reject(kid.rid, str(e))
                    done.append(kid.rid)

    def poll(self) -> Tuple[Dict[object, np.ndarray], List[object]]:
        """One scheduling iteration: expire deadlines, refill free
        slots from the queue (preempting under pool pressure), run one
        decode chunk (optionally under the watchdog), retire what
        finished. Returns ({rid: new tokens}, [rids done this chunk] —
        finished, rejected, or deadline-expired; rejected/expired rids
        have their reason in self.rejected). A request the slots REJECT
        (e.g. prompt + gen beyond capacity) is reported as finished
        with no tokens — one bad request must never take down the
        serving loop. A PREEMPTED request is in neither list: it
        silently re-queues and its rid keeps streaming on resume.

        overlap=True swaps in the pipeline-aware iteration
        (_poll_overlap): same contract, same streams, with the host
        phases running under the device's compute instead of after
        its readback.

        Every poll is a `sched:poll` phase (poll_ms histogram and
        self-time totals always, its own phases nested under it; the
        Chrome ring's spans when tracing), and delivered tokens drive
        the live ttft_ms / inter_token_ms histograms."""
        with self.tele.poll_span():
            if self.overlap:
                if self._grammar_sync_needed():
                    # spec=0 grammar ticks cannot dispatch-ahead (the
                    # next mask needs the unlanded token): collapse
                    # the pipeline and take the sync iteration —
                    # unconstrained polls return to overlap untouched
                    if not self._pipeline_idle():
                        self._drain(self._carry_out, self._carry_done)
                    carry_out, carry_done = \
                        self._carry_out, self._carry_done
                    self._carry_out, self._carry_done = {}, []
                    out, done = self._poll_sync()
                    for rid, t in carry_out.items():
                        if len(t):
                            self.tele.emit(rid, len(t))
                            self._c_tokens.inc(len(t))
                    for rid in carry_done:
                        self.tele.retire(rid)
                    for rid, t in out.items():
                        _merge_out(carry_out, rid, t)
                    return carry_out, carry_done + done
                return self._poll_overlap()
            return self._poll_sync()

    def _poll_sync(self) -> Tuple[Dict[object, np.ndarray],
                                  List[object]]:
        """The synchronous iteration (poll() has the contract)."""
        done: List[object] = []
        pf_before = self.slots.prefill_forwarded
        with self._lock, self.tele.phase("bookkeep"):
            # the queue-mutating phases run under the submit lock; the
            # decode chunk below does not (submitters may enqueue while
            # the model steps). NOTE: under MONOLITHIC admissions the
            # lock also covers each admission's whole prefill forward
            # (+ first-call compile), stalling cross-thread submit()
            # for its duration and outside the watchdog's reach —
            # chunked prefill (prefill_budget) removes that hold time,
            # since admit_chunked runs no forward at all
            self._expire_deadlines(done)
            with self.tele.phase("admit"):
                self._admit(done)
        if not self.slots.occupied:
            # idle poll, nothing dispatched: drop the stamp so the idle
            # gap is not charged as host time at the next burst's first
            # dispatch (the EMA would jump by the whole wait)
            self._last_mark = None
            self.max_prefill_tokens_per_poll = max(
                self.max_prefill_tokens_per_poll,
                self.slots.prefill_forwarded - pf_before)
            return {}, done
        # a poll with prefills in flight runs ONE mixed tick fusing the
        # decode step with budgeted prompt chunks; otherwise the plain
        # chunk-length slot scan
        if self.slots.prefill_slots:
            step = lambda: self.slots.step_mixed(self.prefill_budget)
            label = (f"scheduler mixed tick "
                     f"(prefill_budget={self.prefill_budget})")
        else:
            ec = self._eff_chunk()
            step = lambda: self.slots.step_chunk(ec)
            label = f"scheduler chunk (chunk={ec})"
        self._mark_dispatch()
        with self.tele.phase("step"):
            if self.watchdog_s is not None:
                from triton_dist_tpu.runtime.stress import watchdog
                try:
                    by_slot, finished = watchdog(step, self.watchdog_s,
                                                 label=label)
                except Exception as e:
                    from triton_dist_tpu.runtime.stress import HangError
                    if isinstance(e, HangError):
                        # record the verdict for stats(), then unwind:
                        # the process is poisoned (stress.watchdog
                        # contract) and the one unacceptable outcome
                        # is a silent freeze
                        self._hang = str(e)
                        self.tele.instant("watchdog_hang", str(e))
                    raise
            else:
                by_slot, finished = step()
        self.max_prefill_tokens_per_poll = max(
            self.max_prefill_tokens_per_poll,
            self.slots.prefill_forwarded - pf_before)
        rid_of = self.slots.rids
        out = {rid_of[b]: t for b, t in by_slot.items()}
        for rid, toks in out.items():
            if len(toks):
                self.tele.emit(rid, len(toks))
                self._c_tokens.inc(len(toks))
        with self.tele.phase("retire"):
            dead = self.slots.grammar_dead
            for b, rid in finished:
                msg = dead.pop(b, None)
                if msg is not None:
                    # dead-end automaton: the stream ends LOUDLY — the
                    # serving layer pops the reason off self.rejected
                    self._reject(rid, msg)
                self.slots.retire(b)
                with self._lock:
                    self._deadline.pop(rid, None)
                self.tele.retire(rid)
                done.append(rid)
        return out, done

    def _land_watchdog(self) -> Tuple[Dict[int, np.ndarray],
                                      List[Tuple[int, object]]]:
        """Land the in-flight tick, watchdogged: under overlap the
        DISPATCH cannot hang (it queues and returns) — the blocking
        readback can, so the hang deadline moves to the landed-tick
        boundary."""
        if self.slots._inflight is None:
            return {}, []
        if self.watchdog_s is not None:
            from triton_dist_tpu.runtime.stress import watchdog
            try:
                return watchdog(self.slots.land, self.watchdog_s,
                                label="scheduler land (overlap)")
            except Exception as e:
                from triton_dist_tpu.runtime.stress import HangError
                if isinstance(e, HangError):
                    self._hang = str(e)
                    self.tele.instant("watchdog_hang", str(e))
                raise
        return self.slots.land()

    def _poll_overlap(self) -> Tuple[Dict[object, np.ndarray],
                                     List[object]]:
        """Pipeline-aware poll (overlap=True — module docstring).

        Non-spec: this poll's bookkeeping (deadlines, admissions) runs
        FIRST, while tick N-1 — dispatched at the end of the previous
        poll — is still computing; only then does the one blocking
        readback land it. Tick N dispatches immediately after, and the
        retire work for N-1's finishers runs under it. Between polls
        the in-flight tick also covers the serving layer's socket
        writes and stats reads.

        spec=K: drafting needs the LANDED history, so the pipeline
        cannot cross the poll boundary. Instead the verify dispatches
        first and the deferred work — the PREVIOUS tick's staged
        retires, deadlines, admissions — runs between dispatch and
        land (the host work hides under the verify forward)."""
        slots = self.slots
        out_acc: Dict[object, np.ndarray] = self._carry_out
        done: List[object] = self._carry_done
        self._carry_out, self._carry_done = {}, []
        pf_before = slots.prefill_forwarded
        tele = self.tele
        if slots.spec:
            skip = frozenset(b for b, _ in self._staged)
            with tele.phase("dispatch"):
                if any(b not in skip for b in slots.occupied):
                    if slots.prefill_slots:
                        slots.begin_mixed(self.prefill_budget,
                                          skip=skip)
                    else:
                        slots.begin_chunk(self.chunk, skip=skip)
                    self._mark_dispatch()
                else:
                    self._last_mark = None  # idle: no dispatch stamp
            # deferred bookkeeping — overlapped with the verify: the
            # previous tick's retires (tree inserts + page releases),
            # deadline expiry, admissions (one-tick slot-free delay)
            with tele.phase("retire"):
                for b, rid in self._staged:
                    if slots.rids[b] == rid:
                        slots.retire(b)
                self._staged = []
            with self._lock, tele.phase("bookkeep"):
                self._expire_overlap(out_acc, done)
                with tele.phase("admit"):
                    self._admit(done, out_acc)
            with tele.phase("land"):
                out, finished = self._land_watchdog()
            rid_of = slots.rids
            for b, t in out.items():
                _merge_out(out_acc, rid_of[b], t)
            dead = slots.grammar_dead
            with self._lock:
                for b, rid in finished:
                    msg = dead.pop(b, None)
                    if msg is not None:
                        self._reject(rid, msg)
                    self._deadline.pop(rid, None)
                    done.append(rid)
            self._staged.extend(finished)
        else:
            with self._lock, tele.phase("bookkeep"):
                self._expire_overlap(out_acc, done)
                with tele.phase("admit"):
                    self._admit(done, out_acc)
            with tele.phase("land"):
                out, finished = self._land_watchdog()
            rid_of = slots.rids
            for b, t in out.items():
                _merge_out(out_acc, rid_of[b], t)
            # dispatch tick N before retiring N-1's finishers: the
            # device starts immediately and the retire bookkeeping
            # (radix-tree inserts, page releases) hides under it
            skip = frozenset(b for b, _ in finished)
            with tele.phase("dispatch"):
                if any(b not in skip for b in slots.occupied):
                    if slots.prefill_slots:
                        slots.begin_mixed(self.prefill_budget,
                                          skip=skip)
                    else:
                        slots.begin_chunk(self.chunk, skip=skip)
                    self._mark_dispatch()
                    self._c_ahead.inc()
                else:
                    self._last_mark = None  # idle: no dispatch stamp
            with tele.phase("retire"):
                for b, rid in finished:
                    if slots.rids[b] == rid:
                        slots.retire(b)
                    with self._lock:
                        self._deadline.pop(rid, None)
                    done.append(rid)
        # drains during the phases above landed into the carry buffers
        for rid, t in self._carry_out.items():
            _merge_out(out_acc, rid, t)
        done.extend(self._carry_done)
        self._carry_out, self._carry_done = {}, []
        self.max_prefill_tokens_per_poll = max(
            self.max_prefill_tokens_per_poll,
            slots.prefill_forwarded - pf_before)
        # lifecycle: token deliveries first (a finishing stream's last
        # chunk must land its ttft/inter-token samples before the
        # retired event pops its record), then the final transitions
        # ({rejected, expired, cancelled} rids already recorded their
        # status — the repeat retire no-ops)
        for rid, t in out_acc.items():
            if len(t):
                tele.emit(rid, len(t))
                self._c_tokens.inc(len(t))
        for rid in done:
            tele.retire(rid)
        return out_acc, done

    def run(self, requests) -> Dict[object, np.ndarray]:
        """Drive a batch of requests to completion (the test/bench
        harness loop; a server calls poll() itself to interleave
        streaming I/O). Returns {rid: tokens [gen_len]}."""
        for r in requests:
            if not self.submit(r):
                raise RuntimeError(
                    f"queue full (max_queue={self.max_queue}); run() "
                    f"has no retry loop — submit through a server")
        acc: Dict[object, list] = {r.rid: [] for r in requests}
        while not self.idle:
            out, _ = self.poll()
            for rid, toks in out.items():
                # setdefault: an n>1 request streams under its (rid, k)
                # fork children, not the submitted rid
                acc.setdefault(rid, []).extend(toks.tolist())
        return {rid: np.asarray(t, np.int64) for rid, t in acc.items()}
