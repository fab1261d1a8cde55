#!/usr/bin/env python
"""Merge several perf_report JSON runs into PERF_OPS_tpu.json by
per-row minimum (the least-contended estimate on a shared
chip — single runs swing +-40%; methodology note embedded in the
output). Degenerate (zero-SOL) rows are taken from the LAST run and
not min-merged, matching the round-3 artifact's convention.

Usage: python tools/merge_perf_runs.py /tmp/perf_run_*.json
"""
import json
import sys


def main(paths):
    runs = [json.load(open(p)) for p in paths]
    base = runs[-1]
    by_op = {}
    for run in runs:
        for row in run["ops"]:
            key = row["op"]
            cur = by_op.get(key)
            if row.get("achieved_us") is None:
                # degenerate rows: keep overwriting -> LAST run wins
                if cur is None or cur.get("achieved_us") is None:
                    by_op[key] = row
                continue
            if (cur is None or cur.get("achieved_us") is None
                    or row["achieved_us"] < cur["achieved_us"]):
                by_op[key] = row
    ops = []
    for row in base["ops"]:
        r = dict(by_op[row["op"]])
        if r.get("achieved_us") and r.get("sol_us"):
            r["sol_frac"] = r["sol_us"] / r["achieved_us"]
        ops.append(r)
    out = {
        "env": base["env"],
        "note": ("rows with a nonzero SOL are the per-row MIN over "
                 f"{len(runs)} full report runs on a shared "
                 "chip (same code, same methodology: data-chained fori "
                 "loops, pooled-min slopes; single runs swing +-40% in "
                 "multi-minute contention windows, so the per-row "
                 "minimum is the least-contended estimate). ndev=1 "
                 "pure-collective rows are DEGENERATE (the op is "
                 "near-identity) and are NOT min-merged."),
        "ops": ops,
    }
    with open("PERF_OPS_tpu.json", "w") as f:
        json.dump(out, f, indent=1)
    for r in ops:
        frac = r.get("sol_frac")
        print(f"{r['op']:24s} {r.get('achieved_us') or 0:9.2f} us  "
              f"{'' if frac is None else f'{frac:.3f} SOL'}")
    print("wrote PERF_OPS_tpu.json")


if __name__ == "__main__":
    main(sys.argv[1:])
