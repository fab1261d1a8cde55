"""Serving telemetry: live latency histograms, a Prometheus scrape,
and a perfetto-ready poll-loop timeline.

The serving stack's observability substrate (runtime/telemetry.py)
gives every scheduler a METRICS REGISTRY — stats() is one deep,
point-in-time snapshot with live ``ttft_ms`` / ``inter_token_ms``
p50/p95/p99 histograms (the Sarathi-Serve tail numbers, measured on
real traffic instead of an offline bench) — and, with tracing on, a
Chrome-trace-event TIMELINE of the poll loop: host phase spans
(bookkeep/dispatch/land/retire/drafter), device-occupancy spans
(dispatch → readback landing), and instants for preemptions and
watchdog fires. Load the dump at https://ui.perfetto.dev or summarize
it in the terminal with tools/trace_view.py.

This demo serves a small burst through a real TokenServer (paged pool,
prefix cache, overlap scheduler, tracing ON) and then:
- fetches the live stats snapshot in-protocol ({"op": "stats"}),
- scrapes the Prometheus ``/metrics`` listener,
- dumps the poll timeline (TDTPU_TRACE) and summarizes it.

Telemetry is exact-by-construction: tracing is host-side only, so the
token streams here are bitwise identical to a telemetry-off server
(asserted in tests/test_telemetry.py).

Run on CPU (no TPU needed):
  JAX_PLATFORMS=cpu \
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python examples/16_telemetry.py
"""

import json
import os
import socket
import sys
import tempfile
import threading

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import _common  # noqa: E402
_common.bootstrap()              # widen the CPU substrate BEFORE jax loads

# the TDTPU_TRACE convention: tracing on + dump-on-exit to this path
TRACE = os.path.join(tempfile.gettempdir(), "tdtpu_example16_trace.json")
os.environ["TDTPU_TRACE"] = TRACE


def main():
    from triton_dist_tpu.models import AutoLLM, Engine
    from triton_dist_tpu.models.config import tiny_qwen3
    from triton_dist_tpu.runtime import initialize_distributed
    from triton_dist_tpu.serving import (ByteTokenizer, TokenServer,
                                         request_stream)

    ctx = initialize_distributed()
    cfg = tiny_qwen3(ctx.tp_size())
    model = AutoLLM.from_config(cfg, ctx.mesh)
    eng = Engine(model, max_seq=64, backend="xla")
    tok = ByteTokenizer(cfg.vocab_size)

    srv = TokenServer(eng, tok, batch=4, chunk=4, paged=True, page=8,
                      prefill_budget=8, overlap=True, metrics_port=0)
    th = threading.Thread(target=srv.serve_forever, daemon=True)
    th.start()

    # --- a small burst: 4 concurrent clients, shared system prompt
    system = "You are a helpful TPU. "
    prompts = [system + q for q in ("alpha?", "beta!", "gamma.",
                                    "delta;")]
    results = {}

    def client(i):
        toks = []
        for msg in request_stream("127.0.0.1", srv.port, prompts[i],
                                  gen_len=12, seed=i):
            if msg.get("done"):
                break
            toks.extend(msg["token_ids"])
        results[i] = toks

    # two waves: the second admits AFTER the first retired its pages
    # into the radix tree, so its shared system prompt is a cache hit
    for wave in ((0, 1), (2, 3)):
        cts = [threading.Thread(target=client, args=(i,)) for i in wave]
        for t in cts:
            t.start()
        for t in cts:
            t.join(timeout=600)
    assert all(len(results[i]) == 12 for i in range(4))
    print(f"served {len(results)} streams x 12 tokens in two waves")

    # --- the live latency histograms, fetched in-protocol
    with socket.create_connection(("127.0.0.1", srv.port)) as s:
        f = s.makefile("rw", encoding="utf-8", newline="\n")
        f.write(json.dumps({"op": "stats"}) + "\n")
        f.flush()
        st = json.loads(f.readline())["stats"]
    print('{"op": "stats"} snapshot (live, per-request-derived):')
    for key in ("ttft_ms", "inter_token_ms", "poll_ms"):
        m = st[key]
        print(f"  {key:<15s} n={m['count']:<4d} p50={m['p50']:<8g} "
              f"p95={m['p95']:<8g} p99={m['p99']:g}")
    print(f"  prefix-cache hit_rate={st['hit_rate']:.2f} "
          f"(shared system prompt), host_ms_per_poll="
          f"{st['host_ms_per_poll']:.2f}")

    # --- Prometheus text exposition (what a scraper would ingest)
    with socket.create_connection(("127.0.0.1", srv.metrics_port)) as s:
        s.sendall(b"GET /metrics HTTP/1.0\r\n\r\n")
        raw = b""
        while chunk := s.recv(65536):
            raw += chunk
    body = raw.split(b"\r\n\r\n", 1)[1].decode()
    assert "tdtpu_ttft_ms_bucket" in body
    wanted = ("tdtpu_requests_retired", "tdtpu_ttft_ms_count",
              "tdtpu_engine_decode_dispatches")
    print(f"GET /metrics -> {len(body.splitlines())} exposition lines, "
          f"e.g.:")
    for line in body.splitlines():
        if line.split(" ")[0].split("{")[0] in wanted:
            print(f"  {line}")

    # --- stop the server: TDTPU_TRACE makes it dump the timeline
    srv.stop()
    th.join(timeout=60)
    with open(TRACE) as f:
        dump = json.load(f)
    print(f"poll-loop timeline dumped to {TRACE} "
          f"({len(dump['traceEvents'])} events — load in "
          f"https://ui.perfetto.dev), summary:")
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "trace_view", os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools", "trace_view.py"))
    tv = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tv)
    print("  " + tv.summarize(dump, top_k=3).replace("\n", "\n  "))
    print("OK")


if __name__ == "__main__":
    main()
