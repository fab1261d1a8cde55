"""Serving telemetry (runtime/telemetry.py): histogram math, the
deep-snapshot thread contract, and the two hard guarantees the
scheduler integration makes — telemetry-on token streams are BITWISE
identical to telemetry-off across {greedy, sampled, spec=K} x
{contiguous, paged+prefix-cache+host-tier, overlap}, and tracing
compiles ZERO new XLA programs (same churn-guard style as
test_overlap_no_new_programs).

The TokenServer integration test drives a real socket burst and
asserts the full surfacing story: live ttft_ms / inter_token_ms
histograms in stats(), the in-protocol {"op": "stats"} fetch, the
Prometheus /metrics exposition, and the TDTPU_TRACE dump being
perfetto-loadable (traceEvents with poll + device spans) and
summarizable by tools/trace_view.py.
"""

import json
import logging
import socket
import threading

import jax
import numpy as np
import pytest

from triton_dist_tpu.models import (AutoLLM, ContinuousScheduler, Engine,
                                    Request)
from triton_dist_tpu.models.config import tiny_qwen3
from triton_dist_tpu.runtime.telemetry import (COMPILE_STAGES, Counter,
                                               Gauge, Histogram,
                                               MetricsRegistry, Telemetry,
                                               prometheus_text)

mesh = None
_ENGINES = {}


def setup_module(module):
    global mesh
    n = len(jax.devices())
    mesh = jax.make_mesh((n,), ("tp",))


def _engine(mode):
    """One model + engine per sampling mode, shared across tests (the
    compiled programs are the expensive part of this file)."""
    if mode not in _ENGINES:
        cfg = tiny_qwen3(mesh.shape["tp"])
        model = AutoLLM.from_config(cfg, mesh)
        ekw = dict(sampling="top_k", temperature=0.8) \
            if mode == "sampled" else {}
        _ENGINES[mode] = (cfg, Engine(model, max_seq=64, backend="xla",
                                      **ekw))
    return _ENGINES[mode]


def _mixed_requests(cfg, shared_prefix=None, seed=0):
    rng = np.random.RandomState(seed)
    spec = [(5, 6), (20, 8), (3, 4), (12, 10), (7, 9)]
    out = []
    for i, (L, g) in enumerate(spec):
        ids = rng.randint(0, cfg.vocab_size, size=(L,)).astype(np.int32)
        if shared_prefix is not None and i % 2:
            ids = np.concatenate([shared_prefix, ids]).astype(np.int32)
        out.append(Request(rid=i, ids=ids, gen_len=g, seed=100 + i))
    return out


# ----------------------------------------------------------------------
# histogram / registry unit tests
# ----------------------------------------------------------------------

def test_histogram_bucket_boundaries():
    h = Histogram("h", lo=1.0, hi=16.0, growth=2.0)
    # edges [1, 2, 4, 8, 16]; counts = [under, 4 buckets, over]
    np.testing.assert_allclose(h.edges, [1.0, 2.0, 4.0, 8.0, 16.0])
    assert h.counts.shape == (6,)
    for v, want in [(0.5, 0), (0.0, 0), (-3.0, 0), (float("nan"), 0),
                    (1.5, 1), (3.0, 2), (5.0, 3), (15.9, 4),
                    (16.5, 5), (1e9, 5)]:
        before = h.counts[want]
        h.record(v)
        assert h.counts[want] == before + 1, f"v={v} -> bucket {want}"
    assert h.n == 10
    # NaN/negative contribute 0 to the sum, not garbage
    assert h.total == pytest.approx(0.5 + 1.5 + 3 + 5 + 15.9 + 16.5 + 1e9)
    # +inf lands in the overflow sink with its sum clamped to the top
    # edge (one bad sample must not poison the mean)
    h.record(float("inf"))
    assert h.counts[5] == 3
    assert np.isfinite(h.total) and h.snapshot()["sum"] > 0


def test_histogram_quantiles_vs_numpy():
    """Geometric-midpoint quantiles land within sqrt(growth) (~9.3% at
    the default growth) of the exact numpy sample percentile."""
    rng = np.random.RandomState(0)
    samples = rng.lognormal(mean=2.0, sigma=1.2, size=5000)
    h = Histogram("lat")
    for v in samples:
        h.record(v)
    tol = float(np.sqrt(h.growth)) + 1e-9
    for q in (50, 95, 99):
        exact = float(np.percentile(samples, q))
        got = h.quantile(q / 100.0)
        assert exact / tol <= got <= exact * tol, \
            f"p{q}: got {got}, exact {exact}"
    snap = h.snapshot()
    assert snap["count"] == 5000
    assert snap["p50"] <= snap["p95"] <= snap["p99"]
    assert Histogram("empty").quantile(0.99) == 0.0


def test_registry_get_or_create_and_type_conflict():
    reg = MetricsRegistry()
    c = reg.counter("a")
    assert reg.counter("a") is c
    c.inc(3)
    assert reg.snapshot()["a"] == 3
    with pytest.raises(TypeError):
        reg.gauge("a")


def test_registry_snapshot_is_deep():
    """Nothing in snapshot() may alias live mutable state: histogram
    entries are fresh dicts, and mutating the snapshot cannot leak
    back into the registry."""
    reg = MetricsRegistry()
    h = reg.histogram("lat")
    h.record(5.0)
    s1 = reg.snapshot()
    s1["lat"]["count"] = 999
    s1["extra"] = 1
    s2 = reg.snapshot()
    assert s2["lat"]["count"] == 1 and "extra" not in s2
    assert s1["lat"] is not s2["lat"]


def test_prometheus_text_exposition():
    reg = MetricsRegistry()
    reg.counter("reqs").inc(7)
    reg.gauge("depth").set(2.5)
    h = reg.histogram("lat_ms", lo=1.0, hi=16.0, growth=2.0)
    for v in (0.5, 3.0, 100.0):
        h.record(v)
    text = prometheus_text(reg)
    assert "# TYPE tdtpu_reqs counter\ntdtpu_reqs 7" in text
    assert "tdtpu_depth 2.5" in text
    # bucket counts are CUMULATIVE and end at +Inf == _count
    assert 'tdtpu_lat_ms_bucket{le="+Inf"} 3' in text
    assert "tdtpu_lat_ms_count 3" in text
    cums = [int(l.rsplit(" ", 1)[1]) for l in text.splitlines()
            if l.startswith("tdtpu_lat_ms_bucket")]
    assert cums == sorted(cums)


def test_prometheus_label_escaping():
    """Labeled metrics render as `{k="v"}` blocks with backslash /
    double-quote / newline escaped (a hostile label value must not
    corrupt the exposition), share ONE `# TYPE` line per base name,
    and keep distinct registry keys per label set."""
    reg = MetricsRegistry()
    reg.counter("slo_goodput", labels={"slo": "interactive"}).inc(2)
    reg.counter("slo_goodput", labels={"slo": "batch"}).inc(3)
    reg.counter("slo_goodput",
                labels={"slo": 'we"ird\\cl\nass'}).inc(1)
    h = reg.histogram("lat_ms", lo=1.0, hi=16.0, growth=2.0,
                      labels={"slo": "interactive"})
    h.record(3.0)
    text = prometheus_text(reg)
    assert 'tdtpu_slo_goodput{slo="interactive"} 2' in text
    assert 'tdtpu_slo_goodput{slo="batch"} 3' in text
    assert 'tdtpu_slo_goodput{slo="we\\"ird\\\\cl\\nass"} 1' in text
    assert "\nass" not in text.replace("\\nass", "")  # no raw newline
    assert text.count("# TYPE tdtpu_slo_goodput counter") == 1
    assert 'tdtpu_lat_ms_bucket{le="4",slo="interactive"} 1' in text
    assert 'tdtpu_lat_ms_count{slo="interactive"} 1' in text
    # registry keys stay distinct and snapshot-addressable
    snap = reg.snapshot()
    assert snap["slo_goodput{slo=interactive}"] == 2
    assert snap["slo_goodput{slo=batch}"] == 3
    # label variants of one name must agree on the metric type
    with pytest.raises(TypeError):
        reg.gauge("slo_goodput", labels={"slo": "interactive"})
    # GROUPING: v0.0.4 wants ALL samples of one metric name in a
    # single group — label variants registered LATER (with unrelated
    # metrics in between, the configure_slo pattern) must still render
    # contiguously with their unlabeled sibling
    reg2 = MetricsRegistry()
    reg2.counter("reqs").inc(1)
    reg2.gauge("depth").set(2)
    reg2.counter("reqs", labels={"slo": "batch"}).inc(5)
    grouped = prometheus_text(reg2).splitlines()
    i = grouped.index("# TYPE tdtpu_reqs counter")
    assert grouped[i + 1] == "tdtpu_reqs 1"
    assert grouped[i + 2] == 'tdtpu_reqs{slo="batch"} 5'
    assert sum(1 for ln in grouped
               if ln.startswith("# TYPE tdtpu_reqs ")) == 1


def test_per_class_histogram_quantiles_vs_numpy():
    """The per-SLO-class histograms are full Histogram instances: the
    geometric-midpoint quantile bound (sqrt(growth)) holds on them
    exactly as on the aggregate ones."""
    from triton_dist_tpu.runtime.telemetry import Telemetry
    t = Telemetry()
    t.configure_slo({"interactive": {"ttft_target_ms": 200.0,
                                     "itl_target_ms": 50.0}})
    h = t.slo_classes["interactive"].h_ttft
    assert h.labels == {"slo": "interactive"}
    rng = np.random.RandomState(3)
    samples = rng.lognormal(mean=3.0, sigma=1.0, size=4000)
    for v in samples:
        h.record(v)
    tol = float(np.sqrt(h.growth)) + 1e-9
    for q in (50, 95, 99):
        exact = float(np.percentile(samples, q))
        got = h.quantile(q / 100.0)
        assert exact / tol <= got <= exact * tol, \
            f"p{q}: got {got}, exact {exact}"
    # and the registry snapshot carries it under the labeled key
    snap = t.registry.snapshot()
    assert snap["ttft_ms{slo=interactive}"]["count"] == 4000


def test_slo_goodput_judgement():
    """Goodput iff retired normally within BOTH class targets; a late
    first token, a stalled gap, or any non-retired final state is a
    violation — and goodput + violations partition the class's
    finished requests exactly."""
    t = Telemetry()
    t.configure_slo({
        "fast": {"ttft_target_ms": 1e9, "itl_target_ms": 1e9},
        "strict": {"ttft_target_ms": 0.0, "itl_target_ms": 0.0},
    })
    # within targets -> goodput
    t.queued("a", slo="fast")
    t.emit("a", 1)
    t.emit("a", 1)
    t.retire("a")
    # impossible targets -> violation (TTFT > 0.0ms always)
    t.queued("b", slo="strict")
    t.emit("b", 1)
    t.retire("b")
    # cancelled mid-stream -> violation even within targets
    t.queued("c", slo="fast")
    t.emit("c", 1)
    t.retire("c", "cancelled")
    # never emitted (rejected) -> violation
    t.queued("d", slo="fast")
    t.retire("d", "rejected")
    # untagged requests stay out of the partition
    t.queued("e")
    t.emit("e", 1)
    t.retire("e")
    snap = t.registry.snapshot()
    assert snap["slo_goodput{slo=fast}"] == 1
    assert snap["slo_violations{slo=fast}"] == 2
    assert snap["slo_goodput{slo=strict}"] == 0
    assert snap["slo_violations{slo=strict}"] == 1
    # per-class histograms got exactly the tagged samples
    assert snap["ttft_ms{slo=fast}"]["count"] == 2
    assert snap["ttft_ms{slo=strict}"]["count"] == 1
    assert snap["ttft_ms"]["count"] == 4          # aggregate: all
    # an UNKNOWN class registers lazily with no targets instead of
    # crashing the driver (bounded-cardinality policy is serving-side)
    t.queued("f", slo="surprise")
    t.emit("f", 1)
    t.retire("f")
    assert t.registry.snapshot()["slo_goodput{slo=surprise}"] == 1


def test_request_lifecycle_derivations():
    """queued -> emit -> emit -> retire yields one ttft sample, one
    inter-token sample, one e2e sample; repeat retires no-op; trace-off
    keeps no event ring."""
    t = Telemetry()
    t.queued("r")
    t.emit("r", 1)
    t.emit("r", 2)
    t.retire("r")
    t.retire("r")                                  # repeat: no-op
    assert t.h_ttft.n == 1 and t.h_itl.n == 1 and t.h_e2e.n == 1
    assert t.registry.snapshot()["requests_retired"] == 1
    assert t.export()["requests"] == {}            # trace off: no ring
    tt = Telemetry(trace=True)
    tt.queued("r")
    tt.req_event("r", "admitted", 0)
    tt.emit("r", 1)
    tt.retire("r", "cancelled")
    (req,) = tt.export()["requests"].values()
    assert [e[1] for e in req["events"]] == \
        ["queued", "admitted", "first_token", "cancelled"]
    assert req["ttft_ms"] is not None


# ----------------------------------------------------------------------
# bitwise differential: telemetry/tracing must never touch the stream
# ----------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["contiguous", "paged", "overlap"])
@pytest.mark.parametrize("mode", ["greedy", "sampled", "spec"])
def test_streams_bitwise_trace_on_off(mode, kind):
    cfg, eng = _engine(mode)
    skw = {}
    pre = None
    if kind != "contiguous":
        rng = np.random.RandomState(7)
        pre = rng.randint(0, cfg.vocab_size, size=(11,)).astype(np.int32)
        # paged pool + prefix cache + host tier in the mix
        skw = dict(paged=True, page=8, host_pool_pages=16)
    if kind == "overlap":
        skw["overlap"] = True
    if mode == "spec":
        skw["spec"] = 2

    def run(trace):
        return ContinuousScheduler(eng, batch=3, chunk=4, trace=trace,
                                   **skw).run(_mixed_requests(cfg, pre))

    ref, got = run(False), run(True)
    assert set(ref) == set(got)
    for rid in ref:
        np.testing.assert_array_equal(
            got[rid], ref[rid],
            err_msg=f"{mode}/{kind}: rid={rid} diverged trace-on vs off")


class _CompileCounter(logging.Handler):
    """Names of the programs jax logs as compiled (jax_log_compiles)."""

    def __init__(self):
        super().__init__()
        self.names = []

    def emit(self, record):
        msg = record.getMessage()
        if msg.startswith("Compiling "):
            self.names.append(msg.split()[1])


def test_trace_no_new_programs():
    """Jit-cache-churn guard: tracing is host-side only, so a traced
    mixed refill/chunked-prefill soak must compile ZERO programs the
    untraced soak did not already compile."""
    cfg, eng = _engine("greedy")

    def soak(trace):
        sched = ContinuousScheduler(eng, batch=3, chunk=4, paged=True,
                                    page=8, prefill_budget=3,
                                    overlap=True, trace=trace)
        return sched.run(_mixed_requests(cfg, seed=4)), sched

    counter = _CompileCounter()
    logger = logging.getLogger("jax._src.interpreters.pxla")
    logger.addHandler(counter)
    prev = jax.config.jax_log_compiles
    jax.config.update("jax_log_compiles", True)
    try:
        ref, _ = soak(trace=False)       # compiles + warms everything
        n_off = len(counter.names)
        got, sched = soak(trace=True)
        new = counter.names[n_off:]
        assert not new, (f"tracing compiled {len(new)} program(s) the "
                         f"untraced loop never needed: {new}")
    finally:
        jax.config.update("jax_log_compiles", prev)
        logger.removeHandler(counter)
    for rid in ref:
        np.testing.assert_array_equal(got[rid], ref[rid])
    # the traced run produced a loadable timeline with both tracks
    exp = sched.tele.export()
    names = {e.get("name", "") for e in exp["traceEvents"]}
    assert "poll" in names
    assert any(n.startswith("device:") for n in names)


def test_scheduler_stats_has_live_histograms():
    cfg, eng = _engine("greedy")
    sched = ContinuousScheduler(eng, batch=2, chunk=4)
    sched.run(_mixed_requests(cfg)[:3])
    st = sched.stats()
    for key in ("ttft_ms", "inter_token_ms", "poll_ms",
                "request_latency_ms"):
        assert st[key]["count"] > 0, key
        assert st[key]["p50"] <= st[key]["p95"] <= st[key]["p99"]
    assert st["ttft_ms"]["count"] == 3       # one sample per stream
    assert st["requests_retired"] == 3
    json.dumps(st)                           # fully serializable


# ----------------------------------------------------------------------
# the deep-snapshot thread contract (satellite: the old shallow
# dict(sched.stats()) race)
# ----------------------------------------------------------------------

def test_stats_cross_thread_hammer():
    """stats() from a foreign thread while the driver polls: every
    snapshot must serialize cleanly (no dict-resize races, no aliasing
    of scheduler-side mutable state) and counters must be monotonic."""
    cfg, eng = _engine("greedy")
    sched = ContinuousScheduler(eng, batch=3, chunk=4, paged=True,
                                page=8, host_pool_pages=16)
    reqs = _mixed_requests(cfg, seed=2)
    errors = []
    stop = threading.Event()

    def hammer():
        last_retired = 0
        while not stop.is_set():
            try:
                st = sched.stats()
                json.dumps(st)
                assert st["requests_retired"] >= last_retired
                last_retired = st["requests_retired"]
            except Exception as e:          # noqa: BLE001
                errors.append(e)
                return

    threads = [threading.Thread(target=hammer) for _ in range(3)]
    for t in threads:
        t.start()
    try:
        got = sched.run(reqs)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=30)
    assert not errors, f"stats() raced the driver: {errors[0]!r}"
    assert len(got) == len(reqs)
    st = sched.stats()
    assert st["requests_retired"] == len(reqs)


# ----------------------------------------------------------------------
# TokenServer surfacing: live histograms, {"op": "stats"}, /metrics,
# and the TDTPU_TRACE dump (the acceptance-criteria integration run)
# ----------------------------------------------------------------------

def test_token_server_telemetry_surfacing(tmp_path, monkeypatch):
    from triton_dist_tpu.serving import ByteTokenizer, TokenServer, \
        request_stream

    trace_path = str(tmp_path / "trace.json")
    monkeypatch.setenv("TDTPU_TRACE", trace_path)

    cfg, eng = _engine("greedy")
    tok = ByteTokenizer(cfg.vocab_size)
    srv = TokenServer(eng, tok, batch=4, chunk=4, paged=True, page=8,
                      overlap=True, metrics_port=0)
    assert srv.metrics_port
    th = threading.Thread(target=srv.serve_forever, daemon=True)
    th.start()
    prompts = ["alpha prompt", "second one!", "and a third"]
    results = {}

    def client(i):
        toks = []
        for msg in request_stream("127.0.0.1", srv.port, prompts[i],
                                  gen_len=12):
            if msg.get("done"):
                break
            toks.extend(msg["token_ids"])
        results[i] = toks

    cts = [threading.Thread(target=client, args=(i,)) for i in range(3)]
    for t in cts:
        t.start()
    for t in cts:
        t.join(timeout=600)
    assert all(len(results[i]) == 12 for i in range(3))

    # live histograms through the server's stats()
    st = srv.stats()
    assert st["ttft_ms"]["count"] == 3
    assert st["inter_token_ms"]["count"] > 0
    assert st["ttft_ms"]["p50"] <= st["ttft_ms"]["p99"]

    # in-protocol {"op": "stats"}: one JSON reply line, then close
    with socket.create_connection(("127.0.0.1", srv.port),
                                  timeout=30) as s:
        f = s.makefile("rw", encoding="utf-8", newline="\n")
        f.write(json.dumps({"op": "stats"}) + "\n")
        f.flush()
        reply = json.loads(f.readline())
    assert reply["done"] is True
    assert reply["stats"]["ttft_ms"]["count"] == 3
    assert reply["stats"]["requests_retired"] == 3
    # the compile accounting, flat as host_phase_s is
    for key in ("program_compile_s", "program_compile_n"):
        assert set(st[key]) == set(reply["stats"][key])
        assert {"eager/" + stage for stage in
                ("trace", "lower", "backend", "cache_load")} <= set(st[key])

    # Prometheus text exposition over the metrics listener
    with socket.create_connection(("127.0.0.1", srv.metrics_port),
                                  timeout=30) as s:
        s.sendall(b"GET /metrics HTTP/1.0\r\n\r\n")
        raw = b""
        while True:
            b_ = s.recv(65536)
            if not b_:
                break
            raw += b_
    head, body = raw.split(b"\r\n\r\n", 1)
    assert b"200 OK" in head and b"version=0.0.4" in head
    text = body.decode()
    assert 'tdtpu_ttft_ms_bucket{le="+Inf"} 3' in text
    assert "tdtpu_requests_retired 3" in text
    # the process-global registry rides along (Engine dispatch mix,
    # the compile accounting)
    assert "tdtpu_engine_prefill_dispatches" in text
    assert 'tdtpu_program_compile_s{program="eager",stage="backend"}' \
        in text
    assert 'tdtpu_program_compile_n{program="eager",stage="cache_load"}' \
        in text

    srv.stop()
    th.join(timeout=60)

    # TDTPU_TRACE contract: perfetto-loadable dump on exit
    with open(trace_path) as fh:
        dump = json.load(fh)
    names = [e.get("name", "") for e in dump["traceEvents"]]
    assert "poll" in names, "no poll spans in the timeline"
    assert any(n.startswith("device:") for n in names), \
        "no device-occupancy spans"
    assert any(e.get("ph") == "M" for e in dump["traceEvents"])
    assert len(dump["requests"]) == 3
    for req in dump["requests"].values():
        kinds = [e[1] for e in req["events"]]
        assert kinds[:2] == ["accepted", "queued"] \
            and "first_token" in kinds and kinds[-1] == "retired"
        assert req["ttft_ms"] is not None
    assert dump["metrics"]["ttft_ms"]["count"] == 3
    assert "program_compile_n{program=eager,stage=backend}" \
        in dump["metrics"]

    # ... and tools/trace_view.py can summarize it
    import importlib.util
    import os
    spec = importlib.util.spec_from_file_location(
        "trace_view", os.path.join(os.path.dirname(__file__), "..",
                                   "tools", "trace_view.py"))
    tv = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tv)
    text = tv.summarize(dump, top_k=3)
    assert "poll" in text and "ttft" in text.lower()
    assert "eager" in tv.analyze(dump)["programs"]


# ----------------------------------------------------------------------
# the host path's phases (always on) and the lifecycle from accept() to
# the wire, over one served batch with tracing off and one with it on
# ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def served_pair():
    """The same five prompts through a TokenServer with trace off,
    then on (compiles logged round the second): per run the streams,
    the final stats(), the telemetry export, every phase opened and
    the scheduler's device_wait_s."""
    from triton_dist_tpu.serving import ByteTokenizer, TokenServer, \
        request_stream

    cfg, eng = _engine("greedy")
    tok = ByteTokenizer(cfg.vocab_size)
    # gen_len 3 < chunk: retired inside its first chunk, before the wire.
    # No two prompts share a first byte: the reader threads submit in
    # any order, and a cached prefix would make the admissions' eager
    # pad-and-slice shapes follow that order (whichever of two arrives
    # second is admitted from the offset), so the second run would
    # compile what the first did not on a different arrival order.
    work = [("alpha prompt", 12), ("second one!", 9), ("third", 3),
            ("now a fourth one", 10), ("fifth", 6)]

    def serve(trace):
        opened = []
        srv = TokenServer(eng, tok, batch=3, chunk=4, paged=True,
                          page=8, trace=trace)
        tele = srv.sched.tele
        phase = tele.phase

        def spy(name):
            ph = phase(name)
            opened.append((name, ph))
            return ph
        tele.phase = spy
        th = threading.Thread(target=srv.serve_forever,
                              kwargs=dict(max_requests=len(work)),
                              daemon=True)
        th.start()
        streams = {}

        def client(i):
            prompt, gen_len = work[i]
            streams[i] = [t for msg in request_stream(
                "127.0.0.1", srv.port, prompt, gen_len=gen_len)
                for t in msg.get("token_ids", [])]

        cts = [threading.Thread(target=client, args=(i,))
               for i in range(len(work))]
        for t in cts:
            t.start()
        for t in cts:
            t.join(timeout=600)
        th.join(timeout=120)
        assert not th.is_alive()
        srv.stop()
        return dict(streams=streams, stats=srv.stats(),
                    export=tele.export(), opened=opened,
                    device_wait_s=srv.sched.slots.device_wait_s)

    off = serve(False)
    handler = _CompileCounter()
    logger = logging.getLogger("jax._src.interpreters.pxla")
    logger.addHandler(handler)
    prev = jax.config.jax_log_compiles
    jax.config.update("jax_log_compiles", True)
    try:
        on = serve(True)
    finally:
        jax.config.update("jax_log_compiles", prev)
        logger.removeHandler(handler)
    return dict(off=off, on=on, compiled=handler.names,
                gen_lens=[g for _, g in work])


def test_phase_self_times_partition_the_serve_loop(served_pair):
    """Trace off: the per-phase self times sum to the summed durations
    of the `serve:loop` roots (each phase hands its duration to its
    parent, so this holds by construction), `device_wait` is the very
    seconds `device_wait_s` counts, and the loop's counter counts its
    roots."""
    run = served_pair["off"]
    st = run["stats"]
    phases = st["host_phase_s"]
    from triton_dist_tpu.runtime.telemetry import HOST_PHASES
    assert set(phases) == set(HOST_PHASES)
    roots = [ph.dt for name, ph in run["opened"] if name == "loop"]
    assert len(roots) == st["serve_loop_iterations"] \
        == st["host_phase_n{phase=loop}"] > 0
    assert sum(phases.values()) == pytest.approx(sum(roots), rel=1e-9)
    assert phases["device_wait"] == run["device_wait_s"] > 0
    # the labeled series are the same totals, and every phase the
    # served path ran through has exits (the server dispatches ahead:
    # its ticks are `dispatch` and `land`, not the synchronous `step`)
    for name in ("accept_wait", "poll", "wire_write", "probe",
                 "sched_poll", "bookkeep", "admit", "dispatch", "land",
                 "retire", "device_wait"):
        assert st[f"host_phase_s{{phase={name}}}"] == phases[name] > 0
        assert st[f"host_phase_n{{phase={name}}}"] > 0
    json.dumps(st)
    assert run["export"]["requests"] == {}      # trace off: no ring
    assert not any(e.get("ph") == "X"
                   for e in run["export"]["traceEvents"])


def test_lifecycle_from_accept_to_the_wire(served_pair):
    """Trace on: every request's stamps run accepted <= queued <=
    admitted <= first_token <= wire_first, the one that finished inside
    its first chunk included."""
    reqs = served_pair["on"]["export"]["requests"]
    assert len(reqs) == len(served_pair["gen_lens"])
    for rid, req in reqs.items():
        at = {}
        for ms, name, _ in req["events"]:
            at.setdefault(name, ms)
        order = [at[k] for k in ("accepted", "queued", "admitted",
                                 "first_token", "wire_first")]
        assert order == sorted(order), (rid, req["events"])
        assert [e[1] for e in req["events"]].count("wire_first") == 1
    # ... and the Chrome ring has the serve loop's spans beside the
    # scheduler's (bare names, as tools/trace_view.py reads them)
    names = {e.get("name") for e in
             served_pair["on"]["export"]["traceEvents"]}
    assert {"serve:loop", "serve:accept_wait", "serve:poll",
            "serve:wire_write", "serve:probe", "poll", "bookkeep",
            "admit", "dispatch", "land", "device_wait",
            "retire"} <= names


def test_served_streams_and_programs_same_trace_on_off(served_pair):
    off, on = served_pair["off"], served_pair["on"]
    assert on["streams"] == off["streams"]
    assert [len(off["streams"][i]) for i in range(5)] \
        == served_pair["gen_lens"]
    assert not served_pair["compiled"], (
        f"the traced server compiled {served_pair['compiled']}")


# ----------------------------------------------------------------------
# compile accounting: jax's own trace / lower / backend events as
# program_compile_s / _n per role of the program dispatched, and as
# compile:<role> spans on a traced ring
# ----------------------------------------------------------------------

def _compile_series():
    """{(role, stage): (seconds, events)} of the default registry."""
    from triton_dist_tpu.runtime.telemetry import \
        install_compile_accounting
    secs, n = install_compile_accounting().totals()
    return {tuple(k.split("/")): (secs[k], n[k]) for k in secs}


def _grown(before, after):
    """The series that moved: {(role, stage): (seconds, events)}."""
    out = {}
    for key, (s1, n1) in after.items():
        s0, n0 = before.get(key, (0.0, 0))
        if (s1, n1) != (s0, n0):
            out[key] = (s1 - s0, n1 - n0)
    return out


def _prompt(cfg, length, seed):
    rng = np.random.RandomState(seed)
    return rng.randint(0, cfg.vocab_size, size=(length,)).astype(np.int32)


@pytest.fixture(scope="module")
def cold_engine():
    """An engine whose shapes no other test of this file has compiled
    (max_seq 40), and a traced and an untraced scheduler over it, both
    alive while the first request compiles through the traced one:
    per request what grew in the compile series, the wall seconds of
    the call, and the names jax logged as compiled."""
    import time

    cfg = tiny_qwen3(mesh.shape["tp"])
    eng = Engine(AutoLLM.from_config(cfg, mesh), max_seq=40,
                 backend="xla")
    kw = dict(batch=2, chunk=4, paged=True, page=8, prefix_cache=False)
    on = ContinuousScheduler(eng, trace=True, **kw)
    off = ContinuousScheduler(eng, trace=False, **kw)

    handler = _CompileCounter()
    logger = logging.getLogger("jax._src.interpreters.pxla")
    logger.addHandler(handler)
    prev = jax.config.jax_log_compiles
    jax.config.update("jax_log_compiles", True)
    runs = []
    try:
        # two prompts of 5 ids (one suffix bucket), then one of 13
        for rid, length in enumerate((5, 5, 13)):
            before, seen = _compile_series(), len(handler.names)
            t0 = time.time()
            got = on.run([Request(rid=rid, ids=_prompt(cfg, length, rid),
                                  gen_len=6, seed=rid)])
            runs.append(dict(grown=_grown(before, _compile_series()),
                             wall=time.time() - t0,
                             names=handler.names[seen:],
                             tokens=len(got[rid])))
    finally:
        jax.config.update("jax_log_compiles", prev)
        logger.removeHandler(handler)
    return dict(cfg=cfg, eng=eng, on=on, off=off, runs=runs)


def test_first_request_compiles_under_its_programs_roles(cold_engine):
    """The admission and the decode scan each traced, lowered and
    compiled under their own role; no stage took longer than the call,
    nor did all of them together (they partition the compile time)."""
    run = cold_engine["runs"][0]
    assert run["tokens"] == 6
    for role in ("paged_admit", "paged_slot_scan"):
        for stage in ("trace", "lower", "backend"):
            secs, n = run["grown"][(role, stage)]
            assert 0.0 < secs < run["wall"], (role, stage, secs)
            assert n >= 1
    assert sum(s for (_, stage), (s, _) in run["grown"].items()
               if stage != "cache_load") < run["wall"]
    # the eager pad and the slot's arming compile outside any program
    assert run["grown"][("eager", "backend")][1] >= 1


def test_same_shape_again_compiles_nothing(cold_engine):
    """A second prompt of the same length: nothing lowered, compiled or
    loaded under any role. (On the CPU an interpreter callback keeps
    the admission off jit's C++ fast path, so each dispatch re-enters
    jax's tracing cache: one trace event of microseconds, no more.)"""
    grown = cold_engine["runs"][1]["grown"]
    assert not [k for k in grown if k[1] != "trace"], grown
    assert set(grown) <= {("paged_admit", "trace")}, grown
    assert grown.get(("paged_admit", "trace"), (0.0, 0))[0] < 0.05
    assert not cold_engine["runs"][1]["names"]


def test_new_prompt_length_compiles_its_admission_only(cold_engine):
    """13 ids pad to another suffix bucket: one more admission program
    and the eager pad's tiny ones; the decode scan is the one it was."""
    grown = cold_engine["runs"][2]["grown"]
    assert grown[("paged_admit", "backend")][1] == 1
    assert grown[("paged_admit", "lower")][0] > 0.0
    assert grown[("eager", "backend")][1] >= 1
    assert not [k for k in grown if k[0] == "paged_slot_scan"], grown


def test_nested_jit_counts_the_union_not_the_sum():
    """A program that calls a jitted helper twenty times: jax fires the
    helper's trace events inside the outer one. The role's trace
    seconds stay under the call's wall time and under the plain sum of
    the events; the stages together stay under the wall time too."""
    import time

    import jax.monitoring
    import jax.numpy as jnp

    from triton_dist_tpu.runtime.telemetry import register_program_roles

    @jax.jit
    def helper(x, k):
        return jnp.tanh(x * k) + jnp.roll(x, 1)

    def _probe_nested_fn(x):
        for i in range(20):
            x = helper(x[: x.shape[0] - 1], float(i))   # a shape a call
        return x

    raw = []

    def listen(event, secs, **kw):
        if event == "/jax/core/compile/jaxpr_trace_duration":
            raw.append(secs)

    prog = jax.jit(_probe_nested_fn)
    register_program_roles({"probe_nested": prog})
    x = jnp.ones((64,))
    before = _compile_series()
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        t0 = time.time()
        jax.block_until_ready(prog(x))
        wall = time.time() - t0
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    grown = _grown(before, _compile_series())
    trace_s, trace_n = grown[("probe_nested", "trace")]
    assert len(raw) > 20 and trace_n == 1
    assert 0.0 < trace_s < wall
    assert trace_s < sum(raw)
    assert {k[0] for k in grown} == {"probe_nested"}
    assert sum(s for s, _ in grown.values()) < wall
    assert grown[("probe_nested", "backend")][1] == 1


def test_engine_programs_are_the_plain_jits_under_the_names_jax_logs(
        cold_engine):
    """What `tick.admit_ms.steady`'s `^jit__unknown` rests on: nothing
    stands between the engine and its jitted partials (no wrapper: the
    role is read off jax's own trace event), so jax logs every engine
    program the first request compiled as it logs any jitted
    functools.partial, `<unknown>`."""
    import functools

    import jax.numpy as jnp

    def body(k, x):
        return x * k + 1

    x7 = jnp.ones((7,))
    handler = _CompileCounter()
    logger = logging.getLogger("jax._src.interpreters.pxla")
    logger.addHandler(handler)
    prev = jax.config.jax_log_compiles
    jax.config.update("jax_log_compiles", True)
    try:
        jax.jit(functools.partial(body, 3))(x7)
    finally:
        jax.config.update("jax_log_compiles", prev)
        logger.removeHandler(handler)
    assert len(handler.names) == 1 and "unknown" in handler.names[0]
    # the admission and the decode scan, at least
    first = cold_engine["runs"][0]["names"]
    assert first.count(handler.names[0]) >= 2, first
    eng = cold_engine["eng"]
    for prog in (eng._paged_admit, eng._paged_slot_scan,
                 eng._paged_set_table):
        assert type(prog) is type(jax.jit(body)), type(prog)
    assert eng._paged_admit._cache_size() >= 2       # two suffix buckets
    assert eng._paged_slot_scan.__wrapped__.func.__name__ \
        == "_paged_slot_scan_decode_fn"


def test_every_engine_program_has_a_role_under_the_name_jax_traces():
    """The accounting knows a program by the name jax puts in its
    trace event: `traced_name` agrees with jax's own `fun_name` for
    every program of the engine's set, each name leads to the role of
    a program over that function (the contiguous and the paged mixed
    ticks share one), and a jitted function nobody registered is
    `eager`."""
    import jax.numpy as jnp
    from jax._src import util as jax_util

    from triton_dist_tpu.models.engine import _jit_programs
    from triton_dist_tpu.runtime import telemetry as T

    progs = _jit_programs("xla", "greedy", (1.0, 50, 0.9), "xla")
    by_name = {}
    for role, prog in progs.items():
        name = T.traced_name(prog)
        assert name == jax_util.fun_name(prog.__wrapped__), role
        by_name.setdefault(name, []).append(role)
        assert T._ROLES[name] in by_name[name]
    shared = {n: r for n, r in by_name.items() if len(r) > 1}
    assert shared == {
        "_mixed_step_fn": ["slot_mixed", "paged_slot_mixed"],
        "_mixed_verify_fn": ["slot_mixed_verify",
                             "paged_slot_mixed_verify"]}, shared
    assert T._ROLES["_mixed_step_fn"] == "slot_mixed"

    def _probe_nobodys_fn(x):
        return x * 5

    x = jnp.ones((17,))
    before = _compile_series()
    jax.jit(_probe_nobodys_fn)(x)
    grown = _grown(before, _compile_series())
    assert {k[0] for k in grown} == {"eager"}, grown
    assert grown[("eager", "backend")][1] == 1


def test_compile_spans_on_the_traced_ring_only(cold_engine):
    """trace=True: the first request's compiles are `compile:<role>`
    spans (stage and seconds in args) inside a poll span of the ring;
    the untraced scheduler, alive all the while, kept an empty ring."""
    events = cold_engine["on"].tele.export()["traceEvents"]
    polls = [(e["ts"], e["ts"] + e["dur"]) for e in events
             if e.get("ph") == "X" and e.get("name") == "poll"]
    spans = [e for e in events
             if e.get("ph") == "X"
             and str(e.get("name", "")).startswith("compile:")]
    by_role = {}
    for e in spans:
        assert e["tid"] == 0 and e["args"]["stage"] in COMPILE_STAGES
        assert e["args"]["seconds"] == pytest.approx(e["dur"] / 1e6,
                                                     abs=1e-5)
        by_role.setdefault(e["name"], set()).add(e["args"]["stage"])
        # (the ring of a live traced bundle also takes what the process
        # compiles outside its polls: eager ops, other tests' probes)
        if e["name"] in ("compile:paged_admit",
                         "compile:paged_slot_scan"):
            assert any(s - 1e3 <= e["ts"]
                       and e["ts"] + e["dur"] <= t + 1e3
                       for s, t in polls), e
    for role in ("compile:paged_admit", "compile:paged_slot_scan"):
        assert {"trace", "lower", "backend"} <= by_role[role]
    assert "compile:eager" in by_role
    off = cold_engine["off"].tele.export()["traceEvents"]
    assert not [e for e in off if e.get("ph") != "M"]


def test_dispatch_from_a_second_thread_has_its_own_role():
    """Roles are per thread: two threads inside their programs' traces
    at the same moment (a barrier in the traced bodies) are each
    counted under their own role, and the thread that started them is
    still `eager`."""
    import jax.numpy as jnp

    from triton_dist_tpu.runtime.telemetry import (dispatching_role,
                                                   register_program_roles)

    barrier = threading.Barrier(2, timeout=120)
    seen = {}

    def _probe_thread_a_fn(x):
        seen["probe_thread_a"] = dispatching_role()     # at trace time
        barrier.wait()                                  # both traces open
        return x * 2

    def _probe_thread_b_fn(x):
        seen["probe_thread_b"] = dispatching_role()
        barrier.wait()
        return x * 3

    progs = {"probe_thread_a": jax.jit(_probe_thread_a_fn),
             "probe_thread_b": jax.jit(_probe_thread_b_fn)}
    register_program_roles(progs)
    out = {}

    def work(role, n):
        out[role] = np.asarray(progs[role](jnp.ones((n,))))

    before = _compile_series()
    threads = [threading.Thread(target=work, args=(role, 11 + i))
               for i, role in enumerate(progs)]
    for t in threads:
        t.start()
    assert dispatching_role() == "eager"
    for t in threads:
        t.join(timeout=300)
        assert not t.is_alive()
    grown = _grown(before, _compile_series())
    assert seen == {role: role for role in progs}
    for role in progs:
        assert grown[(role, "trace")][1] == 1
        assert grown[(role, "backend")][1] == 1
        assert grown[(role, "trace")][0] > 0.0
    assert out["probe_thread_a"].shape == (11,) \
        and out["probe_thread_b"][0] == 3.0


def test_host_ms_per_poll_leaves_the_compiles_out():
    """A scheduler whose first request compiles everything: the gauge
    is the host's own milliseconds a poll, not the seconds jax
    compiled between two dispatches (program_compile_s has those)."""
    from triton_dist_tpu.runtime.telemetry import thread_compile_seconds

    cfg = tiny_qwen3(mesh.shape["tp"])
    eng = Engine(AutoLLM.from_config(cfg, mesh), max_seq=48,
                 backend="xla")
    sched = ContinuousScheduler(eng, batch=2, chunk=2, paged=True,
                                page=8, prefix_cache=False)
    c0 = thread_compile_seconds()
    sched.run([Request(rid=0, ids=_prompt(cfg, 6, 0), gen_len=4,
                       seed=0)])
    compiled_ms = 1e3 * (thread_compile_seconds() - c0)
    assert compiled_ms > 100.0           # the scan and the admission
    # an EMA with weight 0.2 on the newest poll: one poll that held
    # the compiles would alone read a fifth of them
    assert 0.0 < sched.stats()["host_ms_per_poll"] < 0.1 * compiled_ms
