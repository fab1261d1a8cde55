#!/usr/bin/env python
"""Summarize a TDTPU_TRACE dump without perfetto.

The serving stack (runtime/telemetry.py) dumps Chrome trace-event JSON
on exit — perfetto-loadable, but a terminal answer is often enough.
This CLI reads one dump and prints:

- per-phase HOST time shares (bookkeep/dispatch/land/retire/drafter/
  step as a fraction of total poll time), total DEVICE occupancy, and
  per-PLANE time (every named track beyond host/device — the disagg
  prefill workers each own one),
- the top-k slowest polls (seq + duration — the stalls worth opening
  perfetto for),
- the cross-plane FLOW pairs (route -> prefill compute -> kv_push ->
  kv_install arrow chains) with per-request transfer latency,
- a per-request table (status, tokens, ttft_ms, transfer_ms) plus the
  ttft_ms / inter_token_ms histogram summary from the embedded
  metrics snapshot,
- the fleet HA event line (replica_death / breaker_open /
  breaker_close / router_failover instants) so a resteer or failover
  is visible in the terminal report, not only in perfetto,
- a PROGRAMS table from the compile accounting (runtime/telemetry.py
  `program_compile_s` / `program_compile_n` in the embedded metrics):
  per engine program role the programs compiled, persistent-cache
  hits, trace / lower / backend seconds, and the `compile:<role>`
  spans that fell INSIDE a poll — a compile the warm-up did not cover.

Usage: python tools/trace_view.py /path/to/trace.json [--top 5]
       python tools/trace_view.py /path/to/trace.json --json
--json emits the machine-readable analysis (the `analyze(dump)` dict)
so CI and tools/bench_compare.py can consume traces. No dependencies
beyond the stdlib; importable (`analyze(dump)` / `summarize(dump)`)
so tests and notebooks can reuse the analysis and formatting.
"""

import argparse
import bisect
import json
import re
import sys

_COMPILE_KEY = re.compile(
    r"^program_compile_(s|n)\{program=([^,}]+),stage=([^,}]+)\}$")


def _fmt_ms(us: float) -> str:
    return f"{us / 1e3:8.3f}ms"


def analyze(dump: dict, top_k: int = 5) -> dict:
    """Digest one dumped trace (the dict form of the JSON file) into a
    plain machine-readable dict — the single source both the text
    report and the --json output render. Pure function, stdlib only."""
    events = dump.get("traceEvents", [])
    tracks = {0: "host phases", 1: "device occupancy"}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "thread_name":
            tracks[e.get("tid", 0)] = e.get("args", {}).get(
                "name", str(e.get("tid")))
    spans = [e for e in events if e.get("ph") == "X"]
    polls = [e for e in spans if e.get("name") == "poll"]
    # the PHASE table covers the scheduler's named host phases only —
    # other tid-0 spans (poll itself, the disagg kv_install, which is
    # stamped INSIDE the bookkeep phase) would double-count wall time
    # already attributed to a phase
    _PHASES = ("bookkeep", "dispatch", "land", "retire", "drafter",
               "step")
    host = [e for e in spans
            if e.get("tid") == 0 and e.get("name") in _PHASES]
    device = [e for e in spans if e.get("tid") == 1]
    instants = [e for e in events if e.get("ph") == "i"]
    flows = [e for e in events if e.get("ph") in ("s", "t", "f")]

    poll_total = sum(e["dur"] for e in polls)
    out = {
        "polls": {"n": len(polls),
                  "total_ms": round(poll_total / 1e3, 3)},
        "phases": {},
        "planes": {},
        "device": {},
        "slowest_polls": [],
        "instants": {},
        "flows": [],
        "requests": [],
        "metrics": {},
    }

    by_phase = {}
    for e in host:
        d, n = by_phase.get(e["name"], (0.0, 0))
        by_phase[e["name"]] = (d + e["dur"], n + 1)
    for name, (dur, n) in by_phase.items():
        out["phases"][name] = {
            "ms": round(dur / 1e3, 3), "n": n,
            "share": round(dur / poll_total, 4) if poll_total else 0.0}

    # per-plane time: every track beyond host(0)/device(1) — the
    # disagg prefill workers — plus the two standard tracks, so the
    # merged timeline's time split reads at a glance
    # a plane's time is the UNION of its span intervals, not their
    # sum — host phase spans nest inside poll spans (and kv_install
    # inside bookkeep), so a plain sum double-counts the host track
    # against the worker tracks this table exists to compare
    by_tid: dict = {}
    for e in spans:
        by_tid.setdefault(e.get("tid", 0), []).append(
            (e["ts"], e["ts"] + e["dur"]))
    plane_ms = {}
    for tid, ivals in by_tid.items():
        ivals.sort()
        busy, cur_s, cur_e = 0.0, None, None
        for s, t in ivals:
            if cur_e is None or s > cur_e:
                if cur_e is not None:
                    busy += cur_e - cur_s
                cur_s, cur_e = s, t
            elif t > cur_e:
                cur_e = t
        if cur_e is not None:
            busy += cur_e - cur_s
        plane_ms[tid] = (busy, len(ivals))
    total_plane = sum(d for d, _ in plane_ms.values())
    for tid, (dur, n) in sorted(plane_ms.items()):
        out["planes"][tracks.get(tid, f"track {tid}")] = {
            "ms": round(dur / 1e3, 3), "spans": n,
            "share": (round(dur / total_plane, 4)
                      if total_plane else 0.0)}

    dev_total = sum(e["dur"] for e in device)
    out["device"] = {
        "ms": round(dev_total / 1e3, 3), "dispatches": len(device),
        "share_of_poll": (round(dev_total / poll_total, 4)
                          if poll_total else 0.0)}

    for e in sorted(polls, key=lambda e: -e["dur"])[:top_k]:
        out["slowest_polls"].append(
            {"seq": e.get("args", {}).get("seq"),
             "ms": round(e["dur"] / 1e3, 3),
             "at_ms": round(e["ts"] / 1e3, 3)})

    for e in instants:
        out["instants"][e["name"]] = out["instants"].get(
            e["name"], 0) + 1

    # fleet HA events pulled out of the generic instant counts: the
    # resteer/failover story of a merged fleet trace, otherwise
    # invisible among the kv_push/kv_install traffic
    _HA = ("replica_death", "breaker_open", "breaker_close",
           "router_failover")
    out["ha_events"] = {k: out["instants"][k] for k in _HA
                        if k in out["instants"]}

    # flow chains (cross-plane request journeys): group by id, order
    # by ts; transfer latency = last push step -> the "f" arrowhead
    # (kv_install). rid rides in args on every event of a chain.
    chains = {}
    for e in sorted(flows, key=lambda e: e["ts"]):
        chains.setdefault(e.get("id"), []).append(e)
    transfer_by_rid = {}
    for fid, evs in sorted(chains.items()):
        rid = next((e.get("args", {}).get("rid") for e in evs
                    if e.get("args", {}).get("rid")), None)
        fin = next((e for e in evs if e["ph"] == "f"), None)
        push = None
        for e in evs:
            if e.get("args", {}).get("at") == "kv_push":
                push = e          # the LAST push wins (retries)
        latency = (round((fin["ts"] - push["ts"]) / 1e3, 3)
                   if fin is not None and push is not None else None)
        out["flows"].append({
            "id": fid, "rid": rid, "events": len(evs),
            "hops": [(tracks.get(e.get("tid", 0), str(e.get("tid"))),
                      e.get("args", {}).get("at") or e["ph"])
                     for e in evs],
            "complete": fin is not None,
            "transfer_ms": latency,
        })
        if rid is not None and latency is not None:
            transfer_by_rid[rid] = latency

    for rid, r in sorted(dump.get("requests", {}).items()):
        out["requests"].append({
            "rid": rid, "status": r.get("status", "?"),
            "tokens": r.get("tokens", 0),
            "ttft_ms": r.get("ttft_ms"),
            "transfer_ms": transfer_by_rid.get(rid),
        })

    metrics = dump.get("metrics", {})
    # programs: the compile accounting's totals per role (process-wide,
    # so a server's dump also holds what ran before it was built) and
    # this ring's compile spans that lie inside one of its polls
    programs = out["programs"] = {}

    def program(role):
        return programs.setdefault(role, {
            "compiled": 0, "cache_hits": 0, "trace_s": 0.0,
            "lower_s": 0.0, "backend_s": 0.0, "in_poll_n": 0,
            "in_poll_ms": 0.0})

    for key, v in metrics.items():
        m = _COMPILE_KEY.match(key)
        if not m:
            continue
        what, role, stage = m.groups()
        row = program(role)
        if what == "s" and stage != "cache_load":
            row[stage + "_s"] = round(v, 6)
        elif what == "n" and stage == "backend":
            row["compiled"] = int(v)
        elif what == "n" and stage == "cache_load":
            row["cache_hits"] = int(v)
    poll_ivals = sorted((e["ts"], e["ts"] + e["dur"]) for e in polls)
    poll_starts = [s for s, _ in poll_ivals]
    for e in spans:
        name = e.get("name", "")
        if e.get("tid") != 0 or not name.startswith("compile:"):
            continue
        # polls do not overlap: the one that began last before the span
        i = bisect.bisect_right(poll_starts, e["ts"]) - 1
        if i >= 0 and e["ts"] + e["dur"] <= poll_ivals[i][1]:
            row = program(name[len("compile:"):])
            row["in_poll_n"] += 1
            row["in_poll_ms"] = round(row["in_poll_ms"]
                                      + e["dur"] / 1e3, 3)

    for key, m in metrics.items():
        base = key.split("{", 1)[0]
        if base in ("ttft_ms", "inter_token_ms", "poll_ms",
                    "kv_transfer_latency_ms") \
                and isinstance(m, dict) and m.get("count"):
            out["metrics"][key] = m
    return out


def summarize(dump: dict, top_k: int = 5) -> str:
    """Render one dumped trace as a terminal report. Pure function:
    no I/O, returns the text."""
    a = analyze(dump, top_k=top_k)
    out = []
    n_inst = sum(a["instants"].values())
    out.append(f"polls: {a['polls']['n']}  total "
               f"{a['polls']['total_ms']:.3f}ms  instants: {n_inst}")

    if a["polls"]["n"]:
        out.append("host phases (share of poll time):")
        for name, p in sorted(a["phases"].items(),
                              key=lambda kv: -kv[1]["ms"]):
            out.append(f"  {name:<12s} {p['ms']:9.3f}ms "
                       f"{p['share']:6.1%}  (n={p['n']})")
        d = a["device"]
        out.append(f"device occupancy: {d['ms']:.3f}ms over "
                   f"{d['dispatches']} dispatches "
                   f"({d['share_of_poll']:.1%} of poll time)")

    # per-plane time (the disagg prefill workers' tracks next to the
    # host/device pair — the merged-timeline split)
    if len(a["planes"]) > 2:
        out.append("planes (share of span time):")
        for name, p in a["planes"].items():
            out.append(f"  {name:<20s} {p['ms']:9.3f}ms "
                       f"{p['share']:6.1%}  ({p['spans']} spans)")

    if a["slowest_polls"]:
        out.append(f"top {len(a['slowest_polls'])} slowest polls:")
        for p in a["slowest_polls"]:
            seq = p["seq"] if p["seq"] is not None else "?"
            out.append(f"  poll #{seq:<6} {_fmt_ms(p['ms'] * 1e3)}  "
                       f"at {p['at_ms']:.3f}ms")

    if a["instants"]:
        out.append("instants: " + "  ".join(
            f"{k}={v}" for k, v in sorted(a["instants"].items())))

    # HA timeline events (replica deaths, breaker trips/readmissions,
    # router failovers) — the "what went wrong and when" line
    if a.get("ha_events"):
        out.append("fleet ha events: " + "  ".join(
            f"{k}={v}" for k, v in sorted(a["ha_events"].items())))

    # cross-plane flow chains (disagg: route -> compute -> kv_push ->
    # kv_install per request)
    if a["flows"]:
        done = sum(1 for fl in a["flows"] if fl["complete"])
        out.append(f"flows: {len(a['flows'])} chains "
                   f"({done} complete)")
        for fl in a["flows"][:top_k]:
            hops = " -> ".join(f"{at}@{plane}"
                               for plane, at in fl["hops"])
            lat = ("-" if fl["transfer_ms"] is None
                   else f"{fl['transfer_ms']:.3f}ms")
            out.append(f"  rid={fl['rid']} transfer={lat}  {hops}")

    if a["requests"]:
        out.append(f"requests ({len(a['requests'])}):")
        out.append(f"  {'rid':<12s} {'status':<10s} {'tokens':>6s} "
                   f"{'ttft_ms':>9s} {'transfer_ms':>11s}")
        for r in a["requests"]:
            ttft = r["ttft_ms"]
            tr = r["transfer_ms"]
            out.append(
                f"  {r['rid']:<12.12s} {r['status']:<10s} "
                f"{r['tokens']:>6d} "
                f"{'-' if ttft is None else format(ttft, '9.3f')} "
                f"{'-' if tr is None else format(tr, '11.3f')}")

    if a["programs"]:
        out.append("programs (compiled / cache hits; trace, lower, "
                   "backend s; compiles inside a poll):")
        for role, p in sorted(a["programs"].items(),
                              key=lambda kv: -(kv[1]["trace_s"]
                                               + kv[1]["lower_s"]
                                               + kv[1]["backend_s"])):
            out.append(
                f"  {role:<24s} {p['compiled']:>4d} / "
                f"{p['cache_hits']:<4d} {p['trace_s']:8.3f} "
                f"{p['lower_s']:8.3f} {p['backend_s']:8.3f}  "
                f"in polls: {p['in_poll_n']} "
                f"({p['in_poll_ms']:.3f}ms)")

    for key, m in a["metrics"].items():
        out.append(f"{key}: n={m['count']} p50={m['p50']} "
                   f"p95={m['p95']} p99={m['p99']}")
    return "\n".join(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace", help="TDTPU_TRACE dump (JSON)")
    ap.add_argument("--top", type=int, default=5,
                    help="how many slowest polls to list")
    ap.add_argument("--json", action="store_true",
                    help="emit the machine-readable analysis instead "
                         "of the text report (CI / bench_compare)")
    args = ap.parse_args(argv)
    with open(args.trace) as f:
        dump = json.load(f)
    if args.json:
        print(json.dumps(analyze(dump, top_k=args.top), indent=1))
    else:
        print(summarize(dump, top_k=args.top))
    return 0


if __name__ == "__main__":
    sys.exit(main())
