"""Serving telemetry: a metrics registry, per-request lifecycle
traces, and a perfetto-ready poll-loop timeline.

The serving stack (models/scheduler.py + serving.py) is a production-
shaped loop — continuous batching, prefix cache + host tier, spec
decode, chunked prefill, dispatch-ahead overlap — and this module is
its observability substrate:

- METRICS REGISTRY: `Counter` / `Gauge` / `Histogram` under a
  `MetricsRegistry`. Histograms are LOG-BUCKETED over fixed numpy
  bins: `record()` is O(1) and allocation-free on the hot path (one
  `math.log`, one in-place bucket increment — no searchsorted, no
  array building), and live p50/p95/p99 come from a cumulative walk
  over ~100 buckets at read time. The scheduler, prefix cache and
  host KV tier publish their counters here, so `stats()` is a DEEP,
  single-point-in-time registry snapshot (every container freshly
  allocated under the registry lock) instead of three hand-maintained
  dicts — the shallow-snapshot race `dict(sched.stats())` used to
  carry is structurally gone. A process-global `default_registry()`
  holds process-wide counters (e.g. Engine dispatch counts) that are
  not per-scheduler.

- REQUEST LIFECYCLE TRACES: `accepted → queued → admitted →
  prefill_chunk*N → first_token → wire_first → tokens →
  preempt/resume → retired/cancelled/expired`, monotonic-stamped per
  request (`accepted` is the return of the serving layer's accept(),
  `wire_first` the flush of the stream's first message; a scheduler
  driven without a server has neither). The always-on half is two derived
  histograms — `ttft_ms` (queued → first token, the Sarathi-Serve
  TTFT) and `inter_token_ms` (gap between consecutive deliveries of a
  stream, the stall a client actually sees) — which previously
  existed only as offline bench rows. The full event ring (bounded,
  oldest-retired-first) is kept only when tracing is ON.

- HOST PHASES (always on): `Telemetry.phase(name)` is the one span
  of the host path, used by TokenServer.serve_forever (`serve:loop`
  per iteration over accept_wait / poll / wire_write / probe /
  idle_sleep) and the scheduler (`sched:poll` over bookkeep / admit /
  step / dispatch / land / retire / drafter / device_wait) alike —
  HOST_PHASES is the whole, fixed set. On exit a phase adds its SELF
  time (its duration less what its child phases covered, so the
  phases of one thread partition its wall time) to the registry's
  `host_phase_s{phase=...}` and bumps `host_phase_n{phase=...}`;
  stats() carries the totals as one flat dict, `host_phase_s`. For
  its extent it is a `jax.profiler.TraceAnnotation` under its
  `serve:` / `sched:` name: an operator who attaches jax.profiler to
  a live TokenServer (start_trace / stop_trace, or the profiler
  server) sees the phases on the serve thread's line, on the clock
  of the device's planes, beside the device's operations.

- POLL-LOOP TIMELINE (tracing on): Chrome trace-event JSON
  (perfetto-loadable — `ui.perfetto.dev`, or `chrome://tracing`)
  with one track for the HOST phases above (the scheduler's under
  their bare names, nested under each poll span; the serve loop's as
  `serve:*`) and one for DEVICE occupancy (dispatch →
  `DecodeSlots._fetch` landing), plus instants for watchdog fires,
  preemptions, drains, and KV demote/promote. This makes the PR-7
  overlap pipeline VISIBLE: the dispatch-ahead bubble structure and
  drain stalls are spans you can measure instead of numbers you
  infer.

- SLO CLASSES + GOODPUT: requests may tag an SLO class at submit
  (`interactive` / `batch` by default — `DEFAULT_SLO_CLASSES`; a
  scheduler passes its own via `configure_slo`). Lifecycle latencies
  then ALSO land in per-class `ttft_ms{slo=...}` /
  `inter_token_ms{slo=...}` histograms, and every final transition is
  judged against the class targets: a request that retired normally
  with TTFT <= `ttft_target_ms` and every inter-token gap <=
  `itl_target_ms` counts into `slo_goodput{slo=...}`, anything else
  (late, stalled, cancelled, expired, rejected) into
  `slo_violations{slo=...}` — the two counters PARTITION the class's
  finished requests exactly. This is the signal an SLO-aware
  admission/preemption policy consumes (DistServe's per-phase SLO
  framing — ROADMAP item 4).

- CROSS-PLANE TIMELINE: beyond the host(0)/device(1) tracks, callers
  can allocate named TRACKS (`track()` — the disagg prefill workers
  each get one) and stamp spans on them (`span()`), and connect
  related work across planes with Chrome trace FLOW events
  (`flow()`: s/t/f arrows — the disagg transfer plane draws
  route -> prefill compute -> kv_push -> kv_install as one arrow
  chain per request, so a single request's journey reads across both
  planes in one merged trace).

- DEVICE-TIME ATTRIBUTION: `mark_dispatch(kind)` always remembers the
  LAST dispatched program kind (one attribute write), so the
  scheduler's coalesced readback can
  attribute its blocking wait per program kind
  (DecodeSlots.device_wait_by_kind: decode/verify/mixed/admit, plus
  the disagg plane's prefill/transfer buckets).

- COMPILE ACCOUNTING (always on, process-wide): every jitted program
  of the engine has a ROLE (`paged_admit`, `paged_slot_scan`, ...),
  registered under the name of the function it traces
  (`register_program_roles`, from engine._jit_programs); whatever else
  is dispatched is role `eager`. `install_compile_accounting()`
  registers ONE set of jax.monitoring listeners that turn jax's own
  compile events into `program_compile_s{program=<role>,stage=trace|
  lower|backend|cache_load}` and `program_compile_n{...}` of the
  default registry. A dispatch that compiles begins with a trace event
  that names its function, so the role is read off jax's own event
  and NOTHING stands on the dispatch path: no wrapper, no frame, no
  write a call (a wrapper round each program read 9-10 s more set-up
  in one cell on the chip, PERF.md PR 41). Seconds are an event's SELF
  time, its span less the events nested in it: a nested jit's trace
  lies inside its caller's, a lowering re-traces inside its own event,
  and none of it is added twice. With tracing on each counted event is
  a `compile:<role>` span on the Chrome ring's host track, inside the
  phase that met it.

ALWAYS ON: the registry, the derived latency histograms, the host
phases (per phase two clock reads, two counter adds and one
TraceAnnotation, which costs under a microsecond while no profiler
session is open) and the compile accounting (its listeners run only
when jax compiles).
`trace=True` ADDS the request event lists and the
Chrome ring; every entry point of those early-outs on `self.trace`.
Either way this module runs no device computation (its one jax call
is the profiler's annotation), so token streams stay BITWISE
identical and zero new XLA programs compile (asserted by
tests/test_telemetry.py). Enable tracing with
`ContinuousScheduler(trace=True)` / `TokenServer(trace=True)` or by
setting `TDTPU_TRACE=path` (the TokenServer also dumps the trace to
that path on exit); summarize dumps with `tools/trace_view.py`
(`--json` for the machine-readable form CI and bench_compare read).
"""

from __future__ import annotations

import functools
import json
import math
import os
import re
import threading
import time
import weakref
from collections import deque
from typing import Dict, List, Optional

import numpy as np
from jax.profiler import TraceAnnotation


def labeled_name(name: str, labels: Optional[Dict[str, str]]) -> str:
    """The registry/snapshot key of a (possibly labeled) metric:
    `name` alone, or `name{k=v,...}` with the labels sorted — compact
    and stable, so stats() consumers can address per-class series
    (e.g. `ttft_ms{slo=interactive}`) without parsing exposition
    syntax."""
    if not labels:
        return name
    inner = ",".join(f"{k}={v}" for k, v in sorted(labels.items()))
    return f"{name}{{{inner}}}"


class Counter:
    """Monotonic event counter. `inc()` is a plain add (GIL-atomic
    enough for the single-writer driver thread; cross-thread writers
    — e.g. busy rejections from reader threads — tolerate the same
    best-effort semantics the raw-int counters always had). A total of
    seconds (`host_phase_s`) grows by floats; every other counter by
    ints."""

    __slots__ = ("name", "help", "labels", "_v")

    def __init__(self, name: str, help: str = "", *,
                 labels: Optional[Dict[str, str]] = None):
        self.name = name
        self.help = help
        self.labels = labels
        self._v = 0

    def inc(self, n: float = 1) -> None:
        self._v += n

    @property
    def value(self) -> float:
        return self._v

    def snapshot(self):
        return self._v


class Gauge:
    """Point-in-time value (pool occupancy, an EMA, a queue depth)."""

    __slots__ = ("name", "help", "labels", "_v")

    def __init__(self, name: str, help: str = "", *,
                 labels: Optional[Dict[str, str]] = None):
        self.name = name
        self.help = help
        self.labels = labels
        self._v = 0.0

    def set(self, v: float) -> None:
        self._v = float(v)

    @property
    def value(self) -> float:
        return self._v

    def snapshot(self):
        return self._v


class Histogram:
    """Log-bucketed latency histogram over FIXED numpy bins.

    Bucket i >= 1 covers [lo * growth**(i-1), lo * growth**i); bucket
    0 is the underflow sink (values below `lo`, zero/negative, NaN),
    the last bucket is the overflow sink (values >= the top edge,
    +inf included — its sum contribution clamps to the top edge so
    one bad sample cannot poison the mean). `record()` is
    O(1) and zero-alloc: the bucket index is pure math
    (log(v) arithmetic against precomputed constants), the increment
    is in-place into a preallocated int64 array — no per-sample numpy
    temporaries, which is what lets the scheduler record on the poll
    hot path without showing up in host_ms_per_poll.

    `quantile(q)` walks the cumulative counts and returns the
    GEOMETRIC MIDPOINT of the bucket holding the rank, so its
    relative error vs the exact sample percentile is bounded by
    sqrt(growth) (~9.3% at the default growth of 2**0.25) —
    tests/test_telemetry.py pins this against numpy.percentile."""

    __slots__ = ("name", "help", "labels", "lo", "growth", "edges",
                 "counts", "n", "total", "_log_lo", "_inv_log_g",
                 "_nbins", "_top")

    def __init__(self, name: str, help: str = "", *, lo: float = 0.01,
                 hi: float = 6e5, growth: float = 2.0 ** 0.25,
                 labels: Optional[Dict[str, str]] = None):
        if not (lo > 0 and hi > lo and growth > 1.0):
            raise ValueError(f"bad histogram bounds: lo={lo} hi={hi} "
                             f"growth={growth}")
        self.name = name
        self.help = help
        self.labels = labels
        self.lo = float(lo)
        self.growth = float(growth)
        self._nbins = int(math.ceil(
            math.log(hi / lo) / math.log(growth)))
        # fixed bin EDGES [lo, lo*g, ..., lo*g^nbins]; counts has an
        # underflow slot in front and an overflow slot behind
        self.edges = self.lo * self.growth ** np.arange(
            self._nbins + 1, dtype=np.float64)
        self.counts = np.zeros((self._nbins + 2,), np.int64)
        self.n = 0
        self.total = 0.0
        self._log_lo = math.log(self.lo)
        self._inv_log_g = 1.0 / math.log(self.growth)
        self._top = float(self.edges[-1])

    def record(self, v) -> None:
        v = float(v)
        if not v >= self.lo:        # below lo, zero, negative, or NaN
            i = 0
            v = max(v, 0.0) if v == v else 0.0
        elif v >= self._top:        # overflow sink (reached directly:
            i = self._nbins + 1     # int(log(+inf)) would raise, and
            if v == math.inf:       # an inf sum poisons the snapshot
                v = self._top       # — clamp ONLY the non-finite case
        else:
            i = int((math.log(v) - self._log_lo) * self._inv_log_g) + 1
            if i > self._nbins:
                i = self._nbins + 1
        self.counts[i] += 1
        self.n += 1
        self.total += v

    def quantile(self, q: float) -> float:
        """q in [0, 1]: geometric-midpoint estimate of the q-th sample
        quantile (0.0 when empty; clamped to [lo, top edge])."""
        if self.n == 0:
            return 0.0
        rank = q * (self.n - 1)
        c = 0
        for i in range(len(self.counts)):
            c += int(self.counts[i])
            if c > rank:
                if i == 0:
                    return float(self.edges[0])
                if i > self._nbins:
                    return float(self.edges[-1])
                return float(math.sqrt(self.edges[i - 1]
                                       * self.edges[i]))
        return float(self.edges[-1])

    def snapshot(self) -> dict:
        """Fresh scalars only — safe to hold across further records."""
        n = self.n
        return {
            "count": int(n),
            "sum": round(float(self.total), 3),
            "mean": round(float(self.total) / n, 3) if n else 0.0,
            "p50": round(self.quantile(0.50), 3),
            "p95": round(self.quantile(0.95), 3),
            "p99": round(self.quantile(0.99), 3),
        }


class MetricsRegistry:
    """Named metrics with get-or-create accessors and DEEP snapshots.

    snapshot() returns {name: scalar | fresh dict} built entirely
    under the registry lock — nothing in the returned structure
    aliases live mutable state, so callers (the serving layer's
    done-messages, the /metrics listener, cross-thread stats()
    readers) can iterate/serialize it while the driver keeps
    recording. The lock is reentrant and exposed (`.lock`) so the
    scheduler can bundle its own point-in-time gauge refresh with the
    snapshot into one consistent cut."""

    def __init__(self):
        self.lock = threading.RLock()
        self._metrics: "Dict[str, object]" = {}

    def _get(self, name: str, cls, help: str, labels=None, **kw):
        key = labeled_name(name, labels)
        with self.lock:
            m = self._metrics.get(key)
            if m is None:
                m = self._metrics[key] = cls(name, help,
                                             labels=labels, **kw)
            elif type(m) is not cls:
                raise TypeError(
                    f"metric {key!r} already registered as "
                    f"{type(m).__name__}, not {cls.__name__}")
            return m

    def counter(self, name: str, help: str = "", *,
                labels: Optional[Dict[str, str]] = None) -> Counter:
        return self._get(name, Counter, help, labels)

    def gauge(self, name: str, help: str = "", *,
              labels: Optional[Dict[str, str]] = None) -> Gauge:
        return self._get(name, Gauge, help, labels)

    def histogram(self, name: str, help: str = "", *,
                  labels: Optional[Dict[str, str]] = None,
                  **kw) -> Histogram:
        return self._get(name, Histogram, help, labels, **kw)

    def snapshot(self) -> dict:
        with self.lock:
            return {name: m.snapshot()
                    for name, m in self._metrics.items()}


_DEFAULT = MetricsRegistry()


def default_registry() -> MetricsRegistry:
    """Process-global registry for metrics that are not per-scheduler
    (Engine dispatch counters, user code). Per-scheduler counters live
    in each scheduler's own registry (`sched.tele.registry`) so two
    schedulers never alias each other's stats."""
    return _DEFAULT


# ----------------------------------------------------------------------
# compile accounting: which program traced, lowered and compiled, and
# for how long (module docstring)
# ----------------------------------------------------------------------

# the role of whatever is not a registered program: the admission's
# eager pad, the scheduler's .at[slot].set scatters, pool allocation, a
# caller's own weight builders
EAGER = "eager"

# jax's compile events by the stage they time. The first three come
# with start and end (jax._src.dispatch.log_elapsed_time) and NEST: a
# jitted function that calls a jitted helper fires the helper's trace
# event inside its own, a lowering re-traces the jnp helpers it meets
# inside its lower event, an op run eagerly while tracing compiles
# inside the trace event. `backend` times the compiler OR the
# persistent cache's load, whichever ran; a load also fires the cache's
# own retrieval event, a duration only, inside it.
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_SPAN_STAGES = {
    _TRACE_EVENT: "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
_CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
COMPILE_STAGES = ("trace", "lower", "backend", "cache_load")

# {name of a traced function: the role of the program that traces it}
_ROLES: Dict[str, str] = {}

class _ThreadCompiles(threading.local):
    """What the listeners keep per thread."""

    role = EAGER        # of the dispatch whose compile events arrive
    compile_s = 0.0     # seconds inside any compile event so far

    def __init__(self):
        # one entry per compile event begun and not ended: the seconds
        # its children have covered
        self.open: List[float] = []


_DISPATCH = _ThreadCompiles()

# the Telemetry bundles built with trace=True that are still alive:
# each gets the compile spans on its ring
_TRACED: "weakref.WeakSet[Telemetry]" = weakref.WeakSet()
_TRACED_LOCK = threading.Lock()


def traced_name(fn) -> Optional[str]:
    """The name jax gives a jitted callable's function in its trace
    event (jax._src.util.fun_name): its `__name__`, through any
    functools.partial."""
    fn = getattr(fn, "__wrapped__", fn)
    while (isinstance(fn, functools.partial)
           and getattr(fn, "__name__", None) is None):
        fn = fn.func
    return getattr(fn, "__name__", None)


def register_program_roles(programs: Dict[str, object]) -> None:
    """{role: jitted program}: whatever jax traces, lowers or compiles
    for a dispatch of one of these is counted under its role. Two
    programs over one function (the contiguous and the paged form of a
    mixed tick) share the role registered first."""
    for role, fn in programs.items():
        name = traced_name(fn)
        if name is not None:
            _ROLES.setdefault(name, role)


def dispatching_role() -> str:
    """The role of the dispatch whose compile events the calling
    thread is in (or was last in)."""
    return _DISPATCH.role


def thread_compile_seconds() -> float:
    """Seconds the calling thread has spent inside jax's trace, lower
    and backend-compile events so far (their union): the scheduler
    takes what a poll's dispatches compiled out of host_ms_per_poll."""
    return _DISPATCH.compile_s


class CompileAccounting:
    """jax's compile events as counters of `registry`, by the role of
    the dispatch they belong to. jax calls a listener on the thread
    that compiles, and a dispatch that compiles anything begins with a
    trace event that names the function traced (even where jax finds
    the trace in its cache): an event with nothing open round it sets
    the thread's role from that name, and the lower and backend events
    of the same dispatch follow it.

    SECONDS are an event's SELF time: its span less what the events
    nested in it covered, whatever their stage (jax records a scalar
    under the event's name when it begins, so the open events of a
    thread are a stack). A stage's seconds are then the union of its
    events less what other stages did inside them, never their sum,
    and the three stages partition the thread's compile time.
    COUNTS (and the ring's `compile:<role>` spans) are of the events
    with nothing open round them, and of every backend event (the
    programs that reached the compiler or the cache): a helper's trace
    inside its caller's, or a lowering's re-traces, are not counted
    again."""

    def __init__(self, registry: MetricsRegistry):
        self.registry = registry
        self._lock = threading.Lock()
        self._series: Dict[tuple, tuple] = {}
        for stage in COMPILE_STAGES:    # `eager` is always there
            self._pair(EAGER, stage)

    def _pair(self, role: str, stage: str) -> tuple:
        pair = self._series.get((role, stage))
        if pair is None:
            lb = {"program": role, "stage": stage}
            r = self.registry
            pair = (r.counter("program_compile_s", "seconds jax spent "
                              "tracing, lowering or compiling (backend: "
                              "the compiler or the persistent cache's "
                              "load; cache_load: that load alone) for "
                              "the dispatches of an engine program",
                              labels=lb),
                    r.counter("program_compile_n", "how many times: "
                              "backend counts the programs that "
                              "reached the compiler or the cache, "
                              "cache_load the hits", labels=lb))
            with self._lock:
                self._series[(role, stage)] = pair
        return pair

    def _on_begin(self, event: str, value, **kw) -> None:
        if event in _SPAN_STAGES:
            tls = _DISPATCH
            if not tls.open and event == _TRACE_EVENT:
                tls.role = _ROLES.get(kw.get("fun_name"), EAGER)
            tls.open.append(0.0)

    def _on_span(self, event: str, start: float, end: float,
                 **kw) -> None:
        stage = _SPAN_STAGES.get(event)
        if stage is None:
            return
        tls = _DISPATCH
        open_ = tls.open
        dur = end - start
        # (a jax that records no scalar at an event's start leaves the
        # stack empty: every event then reads as outermost, and takes
        # its role here)
        inner = open_.pop() if open_ else 0.0
        if open_:
            open_[-1] += dur
        else:
            tls.compile_s += dur
            if event == _TRACE_EVENT:
                tls.role = _ROLES.get(kw.get("fun_name"), EAGER)
        # jax stamps these on the wall clock, the ring runs on the
        # monotonic one
        t1 = time.monotonic() - (time.time() - end)
        self._add(stage, max(dur - inner, 0.0),
                  (t1 - dur, t1) if not open_ or stage == "backend"
                  else None)

    def _on_duration(self, event: str, secs: float, **kw) -> None:
        if event == _CACHE_LOAD_EVENT:
            t1 = time.monotonic()
            self._add("cache_load", secs, (t1 - secs, t1))

    def _add(self, stage: str, secs: float, counted) -> None:
        """`secs` more of `stage` for the thread's role;
        `counted`: the event's (t0, t1) on the monotonic clock where
        it counts as one more, None for a nested one."""
        role = dispatching_role()
        seconds, n = self._pair(role, stage)
        with self._lock:
            seconds.inc(secs)
            if counted:
                n.inc()
        if not counted:
            return
        with _TRACED_LOCK:
            traced = list(_TRACED)
        t0, t1 = counted
        for tele in traced:
            tele.span("compile:" + role, t0, t1, tid=0,
                      args={"stage": stage,
                            "seconds": round(t1 - t0, 6)})

    def totals(self) -> tuple:
        """({"<role>/<stage>": seconds}, {"<role>/<stage>": events}),
        the flat form TokenServer.stats() carries."""
        with self._lock:
            series = list(self._series.items())
        return ({f"{r}/{st}": s.value for (r, st), (s, _) in series},
                {f"{r}/{st}": n.value for (r, st), (_, n) in series})


_ACCOUNTING: Optional[CompileAccounting] = None
_ACCOUNTING_LOCK = threading.Lock()


def install_compile_accounting() -> CompileAccounting:
    """The process's one CompileAccounting, over default_registry():
    built, and its listeners registered with jax.monitoring, at the
    first call (initialize_distributed() and Engine() both call, so a
    caller's weight builders are counted too); the same object after."""
    global _ACCOUNTING
    with _ACCOUNTING_LOCK:
        if _ACCOUNTING is None:
            import jax.monitoring as mon
            acc = CompileAccounting(default_registry())
            mon.register_scalar_listener(acc._on_begin)
            mon.register_event_time_span_listener(acc._on_span)
            mon.register_event_duration_secs_listener(acc._on_duration)
            _ACCOUNTING = acc
        return _ACCOUNTING


_NAME_RE = re.compile(r"[^a-zA-Z0-9_:]")


def escape_label_value(v: str) -> str:
    """Prometheus text-format label-value escaping: backslash, double
    quote and newline must be escaped or a hostile/odd value (an rid,
    an error string) corrupts the whole exposition."""
    return (str(v).replace("\\", "\\\\").replace('"', '\\"')
            .replace("\n", "\\n"))


def _label_block(labels: Optional[Dict[str, str]],
                 extra: Optional[Dict[str, str]] = None) -> str:
    """Render `{k="v",...}` (sorted, values escaped, keys sanitized);
    `extra` merges in (histogram `le`). Empty dict -> empty string."""
    merged: Dict[str, str] = dict(labels or {})
    if extra:
        merged.update(extra)
    if not merged:
        return ""
    inner = ",".join(
        f'{_NAME_RE.sub("_", k)}="{escape_label_value(v)}"'
        for k, v in sorted(merged.items()))
    return "{" + inner + "}"


def prometheus_text(*registries: MetricsRegistry) -> str:
    """Prometheus text exposition (v0.0.4) over one or more
    registries: counters/gauges as single samples, histograms as
    cumulative `_bucket{le=...}` series + `_sum`/`_count`. Names are
    sanitized and prefixed `tdtpu_`; label values are escaped
    (escape_label_value). The v0.0.4 format requires ALL samples of
    one metric name in a single group under one `# TYPE` line, so
    metrics are GROUPED BY BASE NAME first — label variants
    registered later (configure_slo's per-class series) render
    contiguously with their unlabeled sibling, not wherever registry
    insertion order left them."""
    groups: "Dict[str, List[object]]" = {}
    for reg in registries:
        with reg.lock:
            metrics = list(reg._metrics.values())
        for m in metrics:
            name = "tdtpu_" + _NAME_RE.sub("_", m.name)
            groups.setdefault(name, []).append(m)
    lines: List[str] = []
    for name, members in groups.items():
        m0 = members[0]
        kind = ("counter" if isinstance(m0, Counter) else
                "gauge" if isinstance(m0, Gauge) else "histogram")
        lines.append(f"# TYPE {name} {kind}")
        for m in members:
            lb = _label_block(m.labels)
            if isinstance(m, Counter):
                lines.append(f"{name}{lb} {m.value}")
            elif isinstance(m, Gauge):
                lines.append(f"{name}{lb} {m.value:g}")
            elif isinstance(m, Histogram):
                cum = 0
                for i in range(len(m.counts) - 1):
                    cum += int(m.counts[i])
                    le = m.edges[min(i, len(m.edges) - 1)]
                    blk = _label_block(m.labels, {"le": f"{le:g}"})
                    lines.append(f"{name}_bucket{blk} {cum}")
                cum += int(m.counts[-1])
                blk = _label_block(m.labels, {"le": "+Inf"})
                lines.append(f"{name}_bucket{blk} {cum}")
                lines.append(f"{name}_sum{lb} {m.total:g}")
                lines.append(f"{name}_count{lb} {m.n}")
    return "\n".join(lines) + "\n"


# The default SLO classes (ROADMAP item 4: per-request SLO classes
# driving admission/preemption — this module is the measurement half).
# interactive = a human is waiting on the first token and every gap;
# batch = throughput work that only needs to finish eventually.
# Schedulers override via configure_slo / ContinuousScheduler(
# slo_classes=...); targets are milliseconds.
DEFAULT_SLO_CLASSES = {
    "interactive": {"ttft_target_ms": 200.0, "itl_target_ms": 100.0,
                    "priority": 2.0},
    "batch": {"ttft_target_ms": 30000.0, "itl_target_ms": 5000.0,
              "priority": 0.0},
}

# Protection rank for requests with NO slo tag (and ad-hoc classes
# registered without a "priority" target): between the default "batch"
# (0) and "interactive" (2) classes, so untagged traffic is displaced
# before a human-facing stream but after throughput work. A workload
# whose requests all share one class (or are all untagged) sees equal
# priorities everywhere, so every priority-leading sort degenerates to
# the class-blind ordering — the bitwise-differential contract.
UNTAGGED_PRIORITY = 1.0


class _SloClass:
    """One configured SLO class: its targets plus the per-class metric
    handles (created once at configure time, so the emit/retire hot
    paths never take the registry lock)."""

    __slots__ = ("name", "ttft_target_ms", "itl_target_ms", "priority",
                 "h_ttft", "h_itl", "c_good", "c_viol")

    def __init__(self, name: str, targets: dict, registry):
        self.name = name
        self.ttft_target_ms = float(
            targets.get("ttft_target_ms", math.inf))
        self.itl_target_ms = float(
            targets.get("itl_target_ms", math.inf))
        # protection rank: SLO-aware schedulers (preemption-victim
        # choice, prefill-budget splits, router shedding) displace the
        # LOWEST priority first
        self.priority = float(targets.get("priority",
                                          UNTAGGED_PRIORITY))
        lb = {"slo": name}
        self.h_ttft = registry.histogram(
            "ttft_ms", "queued -> first token, per request",
            labels=lb)
        self.h_itl = registry.histogram(
            "inter_token_ms", "gap between consecutive deliveries of "
                              "one stream", labels=lb)
        self.c_good = registry.counter(
            "slo_goodput", "requests retired within every class "
                           "target", labels=lb)
        self.c_viol = registry.counter(
            "slo_violations", "requests that missed a class target or "
                              "never finished cleanly", labels=lb)


class _Req:
    """Per-request lifecycle state: the monotonic stamps the derived
    histograms need (always), plus the SLO class (goodput judgement at
    retire needs the worst inter-token gap, tracked incrementally) and
    the event list (tracing only)."""

    __slots__ = ("t_q", "t_first", "t_last", "n", "ev", "slo",
                 "itl_max")

    def __init__(self, t: float, traced: bool,
                 slo: "Optional[_SloClass]" = None):
        self.t_q = t
        self.t_first: Optional[float] = None
        self.t_last: Optional[float] = None
        self.n = 0
        self.ev: Optional[list] = [] if traced else None
        self.slo = slo
        self.itl_max = 0.0


# The host path's phases: name -> the name of its annotation in a
# profiler trace. `serve:` phases are TokenServer.serve_forever's (the
# root `loop` and its children), `sched:` the scheduler's (`sched_poll`
# is one ContinuousScheduler.poll() and the parent of the rest).
# `accept_wait` is the loop's INTAKE of the requests that its reader
# threads parsed since the last iteration (it waits for nothing; the
# acceptor thread that blocks in accept() opens no phase, or the phases
# would no longer partition the serve thread's wall time); `idle_sleep`
# is the loop's only wait of its own, on the inbox's wake event. The
# set is FIXED: Telemetry seeds a total per phase at construction, so
# cross-thread stats() readers never see the dict resize, and a phase
# not listed here is a KeyError at its first use.
HOST_PHASES = {
    "loop": "serve:loop",
    "accept_wait": "serve:accept_wait",
    "poll": "serve:poll",
    "wire_write": "serve:wire_write",
    "probe": "serve:probe",
    "idle_sleep": "serve:idle_sleep",
    "sched_poll": "sched:poll",
    "bookkeep": "sched:bookkeep",
    "admit": "sched:admit",
    "step": "sched:step",
    "dispatch": "sched:dispatch",
    "land": "sched:land",
    "retire": "sched:retire",
    "drafter": "sched:drafter",
    "device_wait": "sched:device_wait",
}


class _Phase:
    """One open phase of the host path (Telemetry.phase). Always on:
    two clock reads, a `TraceAnnotation` for its extent, and on exit
    its SELF time (its duration minus what its child phases covered)
    added to `host_phase_s{phase=...}`. With tracing on it also lands
    in the Chrome ring, whole. `dt` is the whole duration, readable
    after exit (DecodeSlots._fetch charges device_wait_s with it)."""

    __slots__ = ("_tele", "_name", "_ann", "_t0", "_child", "dt")

    def __init__(self, tele: "Telemetry", name: str):
        self._tele = tele
        self._name = name

    def __enter__(self):
        tele = self._tele
        self._child = 0.0
        self._ann = TraceAnnotation(HOST_PHASES[self._name])
        self._ann.__enter__()
        tele._open.append(self)
        self._t0 = time.monotonic()
        return self

    def __exit__(self, *exc):
        t1 = time.monotonic()
        tele = self._tele
        self.dt = dt = t1 - self._t0
        open_ = tele._open
        if open_ and open_[-1] is self:
            open_.pop()
        elif self in open_:
            # a child was abandoned open (a watchdogged step that hung
            # runs on a thread of its own): unwind past it
            del open_[open_.index(self):]
        if open_:
            open_[-1]._child += dt
        seconds, count = tele._phase_totals[self._name]
        seconds.inc(dt - self._child)
        count.inc()
        if tele.trace:
            self._ring(t1)
        self._ann.__exit__(*exc)
        return False

    def _ring(self, t1: float) -> None:
        # the scheduler's phases keep their bare names in the ring
        # (tools/trace_view.py's phase table reads them)
        self._tele._span(HOST_PHASES[self._name].removeprefix("sched:"),
                         self._t0, t1, tid=0)


class _PollPhase(_Phase):
    """The `sched_poll` phase of one scheduler poll: besides what
    every phase does it records the `poll_ms` histogram (the live twin
    of the host_ms_per_poll EMA) and gives its ring span the poll's
    sequence number (tools/trace_view.py ranks these for the top-k
    slowest polls)."""

    __slots__ = ()

    def __init__(self, tele: "Telemetry"):
        super().__init__(tele, "sched_poll")

    def __exit__(self, *exc):
        super().__exit__(*exc)
        self._tele.h_poll.record(self.dt * 1e3)
        return False

    def _ring(self, t1: float) -> None:
        tele = self._tele
        tele._poll_seq += 1
        tele._span("poll", self._t0, t1, tid=0,
                   args={"seq": tele._poll_seq})


class Telemetry:
    """One scheduler's telemetry bundle: registry + request lifecycle
    + poll timeline (module docstring). The ALWAYS-ON half is the
    registry, the derived latency histograms (`ttft_ms`,
    `inter_token_ms`, `request_latency_ms`, `poll_ms`) and the host
    path's phases (`phase()`: per-phase self-time totals and a
    profiler annotation each) — they are the stats() surface. The
    TRACE half (request event rings, the Chrome timeline's spans and
    instants) is gated on `self.trace` with guarded early-outs.

    Thread contract: histogram/counter records and phases come from
    the driver thread (the serve loop's, which is the one that polls);
    `queued`/`retire` (which resize the live-request dict) and
    `export` take the small internal lock so cross-thread submit()
    and stats dumps never iterate a resizing dict."""

    # retired statuses get their own counters, predeclared so the
    # retire path never takes the registry lock
    _STATUSES = ("retired", "cancelled", "expired", "rejected")

    def __init__(self, registry: Optional[MetricsRegistry] = None,
                 *, trace: bool = False, max_retired: int = 512,
                 max_events: int = 65536):
        self.registry = registry if registry is not None \
            else MetricsRegistry()
        self.trace = bool(trace)
        self._lock = threading.RLock()
        self._t0 = time.monotonic()
        r = self.registry
        self.h_ttft = r.histogram(
            "ttft_ms", "queued -> first token, per request")
        self.h_itl = r.histogram(
            "inter_token_ms", "gap between consecutive deliveries of "
                              "one stream")
        self.h_e2e = r.histogram(
            "request_latency_ms", "queued -> retirement, per request")
        self.h_poll = r.histogram(
            "poll_ms", "scheduler poll duration")
        self._c_status = {s: r.counter("requests_" + s)
                          for s in self._STATUSES}
        # per-phase (self seconds, exits) of the host path, seeded for
        # every phase there is; _open is the stack of phases open now
        self._phase_totals = {
            name: (r.counter("host_phase_s", "self time of a phase of "
                             "the serve loop or the scheduler: a "
                             "thread's phases partition its wall time",
                             labels={"phase": name}),
                   r.counter("host_phase_n", "exits of that phase",
                             labels={"phase": name}))
            for name in HOST_PHASES}
        self._open: List[_Phase] = []
        self._live: Dict[object, _Req] = {}
        self._retired: deque = deque(maxlen=max_retired)
        self._events: deque = deque(maxlen=max_events)
        self._dispatch = None           # pending device-track stamp
        self._poll_seq = 0
        # the kind of the most recent device-program dispatch — set by
        # EVERY mark_dispatch call (one attribute write, trace on or
        # off) so the scheduler's coalesced readback can attribute its
        # blocking wait per program kind (device_wait_by_kind)
        self.last_kind = "step"
        # SLO classes (module docstring): name -> _SloClass. Empty
        # until configure_slo — requests without a class (or before
        # configuration) skip the per-class accounting entirely.
        self.slo_classes: Dict[str, _SloClass] = {}
        # named timeline tracks beyond host(0)/device(1): the disagg
        # prefill workers allocate one each (track())
        self._tracks: Dict[str, int] = {"host phases": 0,
                                        "device occupancy": 1}
        self._next_tid = 2
        if self.trace:
            with _TRACED_LOCK:      # compile spans land on its ring
                _TRACED.add(self)

    # ------------------------------------------------------------------
    # request lifecycle (histograms always; event ring when tracing)
    # ------------------------------------------------------------------

    def _ms(self, t: float) -> float:
        return round((t - self._t0) * 1e3, 3)

    def configure_slo(self, classes: Optional[dict] = None) -> None:
        """Register the SLO classes this bundle judges requests
        against (None = DEFAULT_SLO_CLASSES). Idempotent — re-running
        with the same names reuses the registry metrics; each class
        gets per-class ttft/inter-token histograms plus the
        slo_goodput / slo_violations counter pair."""
        for name, targets in (classes or DEFAULT_SLO_CLASSES).items():
            if name not in self.slo_classes:
                self.slo_classes[name] = _SloClass(
                    str(name), dict(targets or {}), self.registry)

    def _slo_of(self, slo) -> "Optional[_SloClass]":
        """Resolve a submit-time class tag; an UNKNOWN tag registers
        lazily with no targets (never violates on latency, still
        partitions goodput/violations) so a stray class string can
        never crash the driver."""
        if slo is None:
            return None
        cls = self.slo_classes.get(slo)
        if cls is None:
            cls = self.slo_classes[slo] = _SloClass(
                str(slo), {}, self.registry)
        return cls

    def queued(self, rid, slo=None, accepted_at=None) -> None:
        """rid entered the waiting line. accepted_at: the monotonic
        stamp at which the serving layer's accept() returned its
        connection, so a traced lifecycle starts at the program's
        first sight of the request; ttft_ms still runs from here."""
        t = time.monotonic()
        with self._lock:
            rec = self._live.get(rid)
            if rec is None:
                rec = self._live[rid] = _Req(t, self.trace,
                                             self._slo_of(slo))
        if rec.ev is not None:
            if accepted_at is not None:
                rec.ev.append([self._ms(accepted_at), "accepted", None])
            rec.ev.append([self._ms(t), "queued",
                           rec.slo.name if rec.slo else None])

    def req_event(self, rid, name: str, detail=None) -> None:
        """Trace-only annotation on a live request (admitted, resume,
        prefill_chunk, preempt, ...). No-op when tracing is off or the
        rid is unknown (e.g. events for never-queued internals)."""
        if not self.trace:
            return
        rec = self._live.get(rid)
        if rec is None or rec.ev is None:
            return
        rec.ev.append([self._ms(time.monotonic()), name, detail])

    def wire_first(self, rid, n: int) -> None:
        """Trace-only: the serving layer has flushed a message of n
        tokens to rid's socket; stamped `wire_first` where it is the
        stream's first (everything emit() has counted is in it). The
        scheduler retires a request that finishes inside its first
        chunk before the wire sees it, so the retired ring is read
        too."""
        if not self.trace or not n:
            return
        ev = [self._ms(time.monotonic()), "wire_first", int(n)]
        rec = self._live.get(rid)
        if rec is not None:
            if rec.n == n and rec.ev is not None:
                rec.ev.append(ev)
            return
        with self._lock:
            for r, summary in reversed(self._retired):
                if r == rid:
                    if summary["tokens"] == n:
                        summary["events"].append(ev)
                    return

    def emit(self, rid, n: int) -> None:
        """One delivery of n tokens to rid's stream: derives ttft_ms
        (first delivery) / inter_token_ms (the rest) live — into the
        aggregate histograms always, and the request's per-class
        histograms when it carries an SLO class."""
        t = time.monotonic()
        rec = self._live.get(rid)
        if rec is None:
            return
        if rec.t_first is None:
            rec.t_first = t
            ttft = (t - rec.t_q) * 1e3
            self.h_ttft.record(ttft)
            if rec.slo is not None:
                rec.slo.h_ttft.record(ttft)
            if rec.ev is not None:
                rec.ev.append([self._ms(t), "first_token", int(n)])
        else:
            gap = (t - rec.t_last) * 1e3
            self.h_itl.record(gap)
            if rec.slo is not None:
                rec.slo.h_itl.record(gap)
                if gap > rec.itl_max:
                    rec.itl_max = gap
            if rec.ev is not None:
                rec.ev.append([self._ms(t), "tokens", int(n)])
        rec.t_last = t
        rec.n += n

    def retire(self, rid, status: str = "retired") -> None:
        """Final transition; repeat retires of the same rid no-op (a
        rejected rid can reappear in a later done list). An SLO-tagged
        request is judged HERE: goodput iff it retired normally, hit
        first token within ttft_target_ms and never stalled past
        itl_target_ms between tokens; every other final state —
        late, stalled, cancelled, expired, rejected — is a violation.
        The two counters partition the class's finished requests."""
        t = time.monotonic()
        with self._lock:
            rec = self._live.pop(rid, None)
        if rec is None:
            return
        self.h_e2e.record((t - rec.t_q) * 1e3)
        cls = rec.slo
        if cls is not None:
            good = (status == "retired"
                    and rec.t_first is not None
                    and (rec.t_first - rec.t_q) * 1e3
                    <= cls.ttft_target_ms
                    and rec.itl_max <= cls.itl_target_ms)
            (cls.c_good if good else cls.c_viol).inc()
        c = self._c_status.get(status)
        if c is None:
            c = self.registry.counter("requests_" + status)
        c.inc()
        if rec.ev is not None:
            rec.ev.append([self._ms(t), status, None])
            ttft = (round((rec.t_first - rec.t_q) * 1e3, 3)
                    if rec.t_first is not None else None)
            with self._lock:
                self._retired.append(
                    (rid, {"status": status, "tokens": rec.n,
                           "ttft_ms": ttft, "events": rec.ev}))

    # ------------------------------------------------------------------
    # poll-loop timeline (host tid=0, device tid=1): phases always,
    # the Chrome ring when tracing
    # ------------------------------------------------------------------

    def _span(self, name: str, t0: float, t1: float, *, tid: int,
              args: Optional[dict] = None) -> None:
        ev = {"name": name, "ph": "X", "pid": 0, "tid": tid,
              "ts": round((t0 - self._t0) * 1e6, 1),
              "dur": round((t1 - t0) * 1e6, 1)}
        if args:
            ev["args"] = args
        self._events.append(ev)

    def poll_span(self) -> _PollPhase:
        return _PollPhase(self)

    def phase(self, name: str) -> _Phase:
        """One phase of the host path, as a context (HOST_PHASES has
        the names; _Phase what it records). Always on."""
        return _Phase(self, name)

    def phase_seconds(self) -> Dict[str, float]:
        """{phase: self seconds so far}, every phase, unrounded."""
        return {name: c[0].value
                for name, c in self._phase_totals.items()}

    def mark_dispatch(self, kind: str = "step") -> None:
        """Stamp a device-program dispatch; the matching
        `device_land()` (DecodeSlots._fetch) closes the device-track
        occupancy span dispatch -> readback-landing. The kind is
        ALWAYS remembered (`last_kind`, one attribute write) so the
        blocking readback can be attributed per program kind even with
        tracing off."""
        self.last_kind = kind
        if self.trace:
            self._dispatch = (kind, time.monotonic())

    def device_land(self) -> None:
        if not self.trace or self._dispatch is None:
            return
        kind, t0 = self._dispatch
        self._dispatch = None
        self._span("device:" + kind, t0, time.monotonic(), tid=1)

    def track(self, name: str) -> int:
        """Get-or-create a named timeline track (e.g. one per disagg
        prefill worker) and return its tid. Callable from any thread.
        The thread_name metadata is synthesized at export() time from
        the persistent track map — NOT stored in the bounded event
        ring, where a long run's events would evict it and leave the
        track anonymous in the dump."""
        with self._lock:
            tid = self._tracks.get(name)
            if tid is None:
                tid = self._tracks[name] = self._next_tid
                self._next_tid += 1
            return tid

    def span(self, name: str, t0: float, t1: float, *, tid: int = 0,
             args: Optional[dict] = None) -> None:
        """Stamp a complete span on any track from monotonic stamps
        the caller took (the cross-plane entry point: disagg workers
        stamp prefill compute / kv_push on their own tids). No-op when
        tracing is off."""
        if not self.trace:
            return
        self._span(name, t0, t1, tid=tid, args=args)

    def flow(self, name: str, fid: int, *, phase: str = "s",
             tid: int = 0, args: Optional[dict] = None) -> None:
        """One Chrome trace FLOW event: phase "s" starts an arrow
        chain, "t" continues it, "f" ends it (bp="e" binds the arrow
        to the enclosing slice). A shared `fid` joins events into one
        chain ACROSS tracks — the disagg transfer plane uses it to
        draw route -> prefill compute -> kv_push -> kv_install as one
        request's journey over both planes."""
        if not self.trace:
            return
        ev = {"name": name, "cat": "flow", "ph": phase, "id": int(fid),
              "pid": 0, "tid": tid,
              "ts": round((time.monotonic() - self._t0) * 1e6, 1)}
        if phase == "f":
            ev["bp"] = "e"
        if args is not None:
            ev["args"] = args
        self._events.append(ev)

    def instant(self, name: str, detail=None, *, tid: int = 0) -> None:
        """Timeline instant (watchdog fire, preemption, drain stall,
        KV demote/promote, transfer-plane kv_push/kv_install)."""
        if not self.trace:
            return
        ev = {"name": name, "ph": "i", "s": "p", "pid": 0, "tid": tid,
              "ts": round((time.monotonic() - self._t0) * 1e6, 1)}
        if detail is not None:
            ev["args"] = {"detail": detail}
        self._events.append(ev)

    # ------------------------------------------------------------------
    # export
    # ------------------------------------------------------------------

    def export(self) -> dict:
        """The dump payload: perfetto loads it via the standard
        `traceEvents` key and ignores the extra `requests`/`metrics`
        sections tools/trace_view.py summarizes."""
        with self._lock:
            # every track's metadata from the persistent map (ring
            # eviction cannot anonymize a long run's worker tracks)
            meta = [
                {"ph": "M", "pid": 0, "tid": tid,
                 "name": "thread_name", "args": {"name": name}}
                for name, tid in sorted(self._tracks.items(),
                                        key=lambda kv: kv[1])]
            events = meta + list(self._events)
            reqs = {}
            for rid, summary in self._retired:
                reqs[str(rid)] = summary
            for rid, rec in self._live.items():
                if rec.ev is not None:
                    ttft = (round((rec.t_first - rec.t_q) * 1e3, 3)
                            if rec.t_first is not None else None)
                    reqs[str(rid)] = {"status": "live",
                                      "tokens": rec.n,
                                      "ttft_ms": ttft,
                                      "events": list(rec.ev)}
        metrics = self.registry.snapshot()
        if self.registry is not _DEFAULT:
            # the process-wide compile series beside the scheduler's
            # own: tools/trace_view.py's programs table reads them
            for k, v in _DEFAULT.snapshot().items():
                if k.startswith("program_compile_"):
                    metrics.setdefault(k, v)
        return {"traceEvents": events, "displayTimeUnit": "ms",
                "requests": reqs, "metrics": metrics}

    def dump(self, path: str) -> None:
        """Write the export to `path` (the TDTPU_TRACE contract)."""
        with open(path, "w") as f:
            json.dump(self.export(), f)


def splice_trace(out: dict, sub: dict, *, tid_base: int, label: str,
                 dt_us: float) -> None:
    """Splice one Telemetry export into another IN PLACE: `sub`'s
    events land on tracks offset by `tid_base`, timestamps rebased by
    `dt_us` (the difference of the two bundles' _t0 clocks, in µs) so
    cross-plane ordering is real, and track metadata + request records
    are namespaced under `label`. One merge rule for every composite
    timeline: a router splicing its replicas' poll loops
    (fleet/router.py export) and the HA pair splicing its retired
    router generations (fleet/ha.py ReplicatedRouter.export)."""
    events = out["traceEvents"]
    for ev in sub.get("traceEvents", ()):
        ev = dict(ev)
        ev["tid"] = tid_base + int(ev.get("tid", 0))
        if "ts" in ev:
            ev["ts"] = round(ev["ts"] + dt_us, 1)
        if ev.get("ph") == "M":
            ev = dict(ev, args={
                "name": f"{label}:{ev['args']['name']}"})
        events.append(ev)
    requests = out.setdefault("requests", {})
    for k, v in sub.get("requests", {}).items():
        requests[f"{label}:{k}"] = v


def trace_comm_kernel(kernel: str, nbytes) -> None:
    """Comm-kernel trace accounting, called from kernels/* each time a
    comm kernel is BUILT into a program (python call = jit trace
    time): the process-global `comm_kernel_traces` counter the TP
    serving proofs assert, plus per-kernel trace and BYTES-MOVED
    counters (`comm_kernel_builds{kernel=...}` /
    `comm_kernel_trace_bytes{kernel=...}` — distinct base names, so a
    PromQL sum() over the labeled series never double-counts the
    unlabeled aggregate). nbytes is the logical payload the
    collective moves (shape-derived at trace time), so a trace can
    put a bandwidth denominator under each kernel's device-occupancy
    spans."""
    reg = default_registry()
    reg.counter("comm_kernel_traces").inc()
    lb = {"kernel": kernel}
    reg.counter("comm_kernel_builds", labels=lb).inc()
    reg.counter("comm_kernel_trace_bytes", labels=lb).inc(int(nbytes))


def trace_env_enabled() -> bool:
    """The TDTPU_TRACE convention: a non-empty value enables tracing
    (and names the TokenServer's dump path)."""
    return bool(os.environ.get("TDTPU_TRACE"))
