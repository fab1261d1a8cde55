"""`python3 -m benchmark.sweep --workload <open-loop cell> --rates 1,1.5,2
--seconds 40 --seed 7`: find the knee once, on the chip. One process,
one set-up; each rate runs the cell's own mix at that rate for
`--seconds`, lets the requests due in the window finish, and prints one
JSON line: the tails, and whether the backlog grew (the scheduler's
queue depth at the close, and the median TTFT of the window's second
half against its first). The knee is the highest rate whose backlog
does not grow. Not part of a benchmark run."""

from __future__ import annotations

import argparse
import importlib
import json
import sys
import time

import numpy as np

from benchmark import harness, load, spec, traffic


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=7)
    a = ap.parse_args(argv)
    bench = spec.Benchmark()
    wl = bench.workload(a.workload)
    cfg, mix = bench.config(wl["config"]), bench.traffic(wl["traffic"])
    devices = harness.devices_or_die(int(wl["chips"]), True)
    system = importlib.import_module("benchmark.systems." + cfg["system"])
    served = system.Served(cfg, a.seed, devices, trace=False)
    harness.warm_up(system, served, mix,
                     traffic.Deck(mix, a.seed, cfg["vocab_size"],
                                  a.seconds))
    send = lambda p, g: system.request(  # noqa: E731
        served.host, served.port, p, g, timeout=a.seconds + 180.0)
    for i, rate in enumerate(float(r) for r in a.rates.split(",")):
        m = dict(mix, rate_per_s=rate)
        deck = traffic.Deck(m, a.seed + i, cfg["vocab_size"], a.seconds)
        loop = load.make_loop(m, deck, send, a.seed + i, a.seconds)
        t0 = time.perf_counter()
        loop.start()
        time.sleep(max(0.0, t0 + a.seconds - time.perf_counter()))
        st = served.stats()
        in_flight = sum(1 for r in loop.records if r.ended is None)
        loop.join(timeout=150.0)
        recs = list(loop.records)
        ttft = [1e3 * (r.first - r.due) for r in recs if r.first]
        half = len(ttft) // 2
        tpot = [1e3 * (r.last - r.first) / (len(r.tokens) - r.n_first)
                for r in recs if r.ok and len(r.tokens) > r.n_first]
        print(json.dumps({
            "rate_per_s": rate, "sent": len(recs),
            "finished": sum(r.ok for r in recs),
            "queue_depth_at_close": st.get("queue_depth"),
            "in_flight_at_close": in_flight,
            "drain_s": round(time.perf_counter() - t0 - a.seconds, 2),
            "ttft_ms": {"p50": float(np.percentile(ttft, 50)),
                        "p95": float(np.percentile(ttft, 95)),
                        "first_half_p50": float(np.median(ttft[:half])),
                        "second_half_p50": float(np.median(ttft[half:]))},
            "tpot_ms": {"p50": float(np.percentile(tpot, 50)),
                        "p95": float(np.percentile(tpot, 95))},
            "out_tokens_per_s": sum(len(r.tokens) for r in recs)
            / (time.perf_counter() - t0)}), flush=True)
    served.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
