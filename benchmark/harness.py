"""One run of one cell: set-up, warm-up, the measured window, the drain,
the comparison with the reference, the metrics, the result line.

Everything that belongs to one configuration, one traffic mix, one
metric or one limit is a file of its own, found by the name that
`BENCHMARK.json` gives it (see `spec.py`). This module knows none of
their names.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib
import json
import os
import shutil
import sys
import tempfile
import threading
import time
from typing import Optional

import numpy as np

from benchmark import (compare, load, peaks as peaks_mod, spec, trace_reduce,
                       traffic)


def log(**fields) -> None:
    """An earlier line of the run: one JSON object, never the last."""
    print(json.dumps(fields), flush=True)


class CompileCount:
    """Programs that went to the backend compiler (or were loaded from
    the persistent cache: the same event times both)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring
        self._lock = threading.Lock()
        self.n, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **kw):
        if event == self.EVENT:
            with self._lock:
                self.n += 1
                self.seconds += secs

    def take(self):
        with self._lock:
            out = (self.n, round(self.seconds, 3))
            self.n, self.seconds = 0, 0.0
        return out


class GcPauses:
    """The collector's pauses (it holds every thread of the process):
    (seconds, generation) of each collection since the last `take`."""

    def __init__(self):
        self._t, self._seen = None, []
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self._seen.append((time.perf_counter() - self._t,
                               info["generation"]))

    def take(self) -> dict:
        seen, self._seen = self._seen, []
        return {"n": len(seen), "full": sum(g == 2 for _, g in seen),
                "sum_ms": 1e3 * sum(s for s, _ in seen),
                "max_ms": 1e3 * max((s for s, _ in seen), default=0.0)}


@dataclasses.dataclass
class Capture:
    """What a run saw; the readers under `benchmark/readers/` take their
    metrics from it and from nothing else."""
    workload: dict
    config: dict
    mix: dict
    peaks: Optional[dict]
    chips: int
    seconds: float
    setup_s: float
    t0: float
    t1: float
    drain_end: float
    records: list
    stats0: dict
    stats1: dict
    lifecycle: dict
    trace: Optional[trace_reduce.Trace]
    batch: int
    chunk: int

    # --- arithmetic several readers share -----------------------------
    def window_tokens(self):
        """(output tokens received inside the window, keys their decode
        steps attended): token j of a request with an n-token prompt was
        sampled after attending n + j - 1 positions."""
        toks = keys = 0
        for r in self.records:
            j = 0
            n = len(r.prompt)
            for t, k in r.token_times:
                if self.t0 <= t <= self.t1:
                    toks += k
                    keys += k * (n + j) + k * (k - 1) // 2
                j += k
        return toks, keys

    def window_prefill(self):
        """(prompt tokens really computed, keys they attended) for the
        admissions of the window, from the program's counters and the
        prompts whose first token came inside it."""
        d = lambda k: self.stats1.get(k, 0) - self.stats0.get(k, 0)  # noqa
        tokens = d("prompt_tokens") - d("prefill_tokens_skipped")
        keys = sum(len(r.prompt) * (len(r.prompt) + 1) // 2
                   for r in self.records
                   if r.first is not None and self.t0 <= r.first <= self.t1)
        return tokens, keys

    def decode_steps(self) -> int:
        d = (self.stats1.get("engine_decode_dispatches", 0)
             - self.stats0.get("engine_decode_dispatches", 0))
        return d * self.chunk


def devices_or_die(chips: int, require_chip: bool):
    import jax
    devs = jax.devices()
    if require_chip and devs[0].platform != "tpu":
        print(f"benchmark: no TPU (jax.devices()[0] is "
              f"{devs[0].platform} {devs[0].device_kind!r})",
              file=sys.stderr)
        raise SystemExit(1)
    if len(devs) < chips:
        print(f"benchmark: the cell asks for {chips} chips, this machine "
              f"has {len(devs)}", file=sys.stderr)
        raise SystemExit(1)
    return devs[:chips]


def _peak_bytes(devices) -> Optional[int]:
    peak = None
    for d in devices:
        st = d.memory_stats()
        if st and st.get("peak_bytes_in_use") is not None:
            peak = max(peak or 0, int(st["peak_bytes_in_use"]))
    return peak


def warm_up(system, served, mix, deck) -> dict:
    """Every shape the window will use, through the server itself: one
    request of each prompt length of the mix first (each compiles its
    admission program; the first also compiles the decode scan), then
    enough short ones at once to pass through every slot."""
    lens = traffic.prompt_lengths(mix)

    def one(k, n, gen):
        rec = load.Record(index=-1, gen_len=gen, due=time.perf_counter(),
                          prompt=deck.warm_prompt(n, k))
        load.run_request(
            lambda p, g: system.request(served.host, served.port, p, g,
                                        timeout=1500.0), rec)
        return rec

    recs = [one(k, n, 2 * served.chunk) for k, n in enumerate(lens)]
    threads, more = [], []
    for i in range(served.batch):
        rec = load.Record(
            index=-1, gen_len=2 * served.chunk, due=time.perf_counter(),
            prompt=deck.warm_prompt(lens[i % len(lens)], len(lens) + i))
        more.append(rec)
        t = threading.Thread(target=load.run_request, daemon=True, args=(
            lambda p, g: system.request(served.host, served.port, p, g,
                                        timeout=600.0), rec))
        threads.append(t)
        t.start()
    for t in threads:
        t.join(900.0)
    bad = [r.error or "unfinished" for r in recs + more if not r.ok]
    if bad:
        raise RuntimeError(f"warm-up requests failed: {bad[:3]}")
    return {"requests": len(recs) + len(more), "prompt_lengths": lens}


def _trace_plan(seconds: float):
    """The slice of the window that is traced: short, inside it."""
    span = min(4.0, max(0.5, seconds / 5.0))
    start = min(max(1.0, seconds / 4.0), max(0.0, seconds - span - 0.5))
    return start, span


def _read_trace(tr_dir: str, keep: Optional[str]) -> trace_reduce.Trace:
    xplane = trace_reduce.find_xplane(tr_dir)
    tr = trace_reduce.load(xplane)
    if keep:
        os.makedirs(keep, exist_ok=True)
        shutil.copy(xplane, os.path.join(keep, "trace.xplane.pb"))
        with open(os.path.join(keep, "summary.json"), "w") as f:
            json.dump(trace_reduce.summary(tr), f, indent=1)
    return tr


def run_cell(workload_name: str, seed: int, seconds: float, trace: int, *,
             process_start: float, require_chip: bool = True,
             root: Optional[str] = None, faults=None,
             keep_trace: Optional[str] = None, drain_s: float = 60.0,
             control=()) -> dict:
    """Run the cell and return the result object (the caller prints it).
    `require_chip=False` is for the benchmark's own self-check and tests
    on the CPU: the result then names platform cpu and has no metric."""
    bench = spec.Benchmark(root)
    wl = bench.workload(workload_name)
    cfg = bench.config(wl["config"])
    mix = bench.traffic(wl["traffic"])
    chips = int(wl["chips"])
    devices = devices_or_die(chips, require_chip)
    dev0 = devices[0]
    on_chip = dev0.platform == "tpu"
    pk = peaks_mod.load(dev0.device_kind) if on_chip else None
    if traffic.max_tokens(mix) > cfg["engine"]["max_seq"] - 8:
        raise ValueError("the mix's longest request does not fit max_seq")

    compiles = CompileCount()
    system = importlib.import_module("benchmark.systems." + cfg["system"])
    t_a = time.perf_counter()
    served = system.Served(cfg, seed, devices, trace=bool(trace))
    if faults:
        faults(served)
    log(phase="build", seconds=round(time.perf_counter() - t_a, 3),
        weight_bytes=served.weight_bytes, pool_pages=served.pool_pages(),
        compiles=compiles.take(), platform=dev0.platform,
        kind=dev0.device_kind, chips=chips, seed=seed)
    t_b = time.perf_counter()
    deck = traffic.Deck(mix, seed, cfg["vocab_size"], seconds)
    warm = warm_up(system, served, mix, deck)
    log(phase="warm_up", seconds=round(time.perf_counter() - t_b, 3),
        compiles=compiles.take(), **warm)
    if trace:
        served.annotate()

    send = lambda p, g: system.request(  # noqa: E731
        served.host, served.port, p, g, timeout=seconds + 120.0)
    loop = load.make_loop(mix, deck, send, seed, seconds)
    # the set-up's garbage (the tracing of four big programs) is the
    # set-up's to collect: a full collection stops every thread
    gc.collect()
    pauses = GcPauses()
    stats0 = served.stats()
    setup_s = time.time() - process_start
    t0 = time.perf_counter()
    loop.start()
    tr = tr_dir = None
    if trace:
        import jax
        start, span = _trace_plan(seconds)
        time.sleep(max(0.0, t0 + start - time.perf_counter()))
        tr_dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(tr_dir, profiler_options=opts)
        time.sleep(span)
        jax.profiler.stop_trace()
    time.sleep(max(0.0, t0 + seconds - time.perf_counter()))
    t1 = time.perf_counter()
    loop.close()
    stats1 = served.stats()
    in_window_compiles = compiles.take()
    gc_in_window = pauses.take()
    closed = mix["loop"] == "closed"
    if closed:
        # a closed loop is saturated by design: whatever is in flight or
        # queued at the close is cut there (the server, stopped, ends
        # every live stream with a done message) and is compared as far
        # as it got; it is neither a success nor a failure
        served.stop()
    all_back = loop.join(timeout=min(10.0, drain_s) if closed else drain_s)
    drain_end = time.perf_counter()
    records = list(loop.records)
    peak = _peak_bytes(devices)
    lifecycle = served.lifecycle() if trace else {}
    if not closed:
        served.stop()
    if served.errors:
        raise served.errors[0]
    if tr_dir is not None:
        try:
            if on_chip:         # a CPU trace has no device plane to read
                tr = _read_trace(tr_dir, keep_trace)
        finally:
            shutil.rmtree(tr_dir, ignore_errors=True)

    ok = [r for r in records if r.ok]
    # closed loop: what had not ended when the window closed was cut by
    # the benchmark itself, however its stream then ended
    cut = [r for r in records if closed and not r.ok
           and (r.ended is None or r.ended >= t1)]
    n_failed = len(records) - len(ok) - len(cut)
    lateness = [1e3 * (r.sent - r.due) for r in records]
    # for reading a run's noise by hand: the gaps between a stream's
    # messages (a host that stood still shows as one far above a chunk)
    # and the requests that set the TPOT tail
    gaps = [1e3 * (b[0] - a[0]) for r in records
            for a, b in zip(r.token_times, r.token_times[1:])]
    slowest = sorted(((1e3 * (r.last - r.first) / (len(r.tokens) - r.n_first),
                       r.gen_len, len(r.prompt)) for r in ok
                      if len(r.tokens) > r.n_first), reverse=True)[:6]
    log(phase="window", seconds=round(t1 - t0, 3),
        drain_seconds=round(drain_end - t1, 3), sent=len(records),
        succeeded=len(ok), failed=n_failed, cut_at_close=len(cut),
        all_back=all_back, compiles_in_window=in_window_compiles,
        gc_in_window=gc_in_window,
        gen_lateness_ms={"p50": float(np.percentile(lateness, 50)),
                         "p95": float(np.percentile(lateness, 95)),
                         "max": float(max(lateness))} if lateness else None,
        server={k: stats1.get(k, 0) - stats0.get(k, 0) for k in (
            "admissions", "hits", "prompt_tokens", "prefill_tokens_skipped",
            "tokens_emitted", "preemptions", "engine_decode_dispatches",
            "engine_prefill_dispatches")},
        server_ttft_ms=stats1.get("ttft_ms"),
        msg_gap_ms={"p50": float(np.percentile(gaps, 50)),
                    "p99": float(np.percentile(gaps, 99)),
                    "max": float(max(gaps))} if gaps else None,
        out_tokens_due=sum(r.gen_len for r in records),
        slowest_tpot=[[round(t, 3), g, n] for t, g, n in slowest],
        errors=sorted({r.error for r in records if r.error})[:3])

    cap = Capture(workload=wl, config=cfg, mix=mix, peaks=pk, chips=chips,
                  seconds=t1 - t0, setup_s=setup_s, t0=t0, t1=t1,
                  drain_end=drain_end, records=records, stats0=stats0,
                  stats1=stats1, lifecycle=lifecycle, trace=tr,
                  batch=served.batch, chunk=served.chunk)
    served.free()
    del served, loop, send

    # --- the comparison that decides `correct` ------------------------
    t_c = time.perf_counter()
    compared = compare.served_tokens(
        bench, wl, cfg, seed, ok + [r for r in cut if r.tokens], n_failed,
        dev0, control=control)
    log(phase="compare", seconds=round(time.perf_counter() - t_c, 3),
        compiles=compiles.take())
    correct = all(c["ok"] for c in compared.values() if "limit" in c)

    metrics = {}
    if on_chip:
        for m in bench.metrics_for(workload_name, trace=bool(trace)):
            val = bench.read_metric(m["name"], cap)
            if val is not None:
                metrics[m["name"]] = {"value": float(val),
                                      "unit": m["unit"]}
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": chips, "memory_peak_bytes": peak}
    result = {"correct": bool(correct), "attempted": len(records),
              "failed": n_failed, "metrics": metrics,
              "device": device}
    if tr is not None and on_chip:
        busy = [trace_reduce.busy_seconds(d) for d in tr.devices]
        device["busy_s"] = float(np.mean(busy))
        device["window_s"] = float(tr.window_s)
        fullest = tr.devices[int(np.argmax(busy))]
        result["breakdown"] = {
            "device_ops": trace_reduce.top_ops(fullest, 10),
            "idle_gaps": trace_reduce.idle_gaps(tr, fullest, 10)}
    result["compared"] = {
        k: {"value": c["value"], "limit": c["limit"]}
        for k, c in compared.items() if "limit" in c}
    for k, c in compared.items():
        if "limit" not in c:
            log(control=k, **c)
            continue
        print(f"compared {k}: value {c['value']!r} limit {c['limit']!r} "
              f"({'within' if c['ok'] else 'OUTSIDE'})", file=sys.stderr,
              flush=True)
    return result
