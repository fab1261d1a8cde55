"""`work_roofline` for a cell whose traced slice does other work a step
than its window does on average (a fill first, then contexts that grow
through the window): the rows and the cached positions of a step are
taken from the output tokens the clients received DURING the slice
(`harness._trace_plan`), not from the whole window's, and set against
the device time of the same slice's events. Rows a step: the slice's
token rate over the stretch that the program's whole executions cover,
by their steps; positions a step: those rows at the mean context of the
slice's tokens."""

import importlib

import numpy as np

from benchmark import harness, peaks, trace_reduce


def slice_tokens(cap):
    """(seconds of the slice, output tokens received inside it, keys
    their decode steps attended)."""
    start, span = harness._trace_plan(cap.seconds)
    a, b = cap.t0 + start, cap.t0 + start + span
    toks = keys = 0
    for r in cap.records:
        j, n = 0, len(r.prompt)
        for t, k in r.token_times:
            if a <= t <= b:
                toks += k
                keys += k * (n + j) + k * (k - 1) // 2
            j += k
    return span, toks, keys


def read(cap, *, work, patterns, program):
    if cap.trace is None or cap.peaks is None:
        return None
    span, toks, keys = slice_tokens(cap)
    if not toks:
        return None
    fn = importlib.import_module("benchmark.work." + work).work
    shares = []
    for dev in cap.trace.devices:
        spans = trace_reduce.whole_executions(cap.trace, dev, program)
        secs, hits = trace_reduce.pattern_seconds(
            trace_reduce.ops_inside(dev, spans), patterns)
        if not spans or not hits or secs <= 0:
            continue
        steps = len(spans) * cap.chunk
        rows = toks / span * (spans[-1][1] - spans[0][0]) / steps
        w = fn(cap.config, cap.chips, steps=steps, rows_per_step=rows,
               kv_tokens_per_step=rows * keys / toks)
        least, _ = peaks.least_seconds(
            w["flops"], w["hbm_bytes"], cap.peaks,
            dtype=cap.config["torch_dtype"], ici_bytes=w["ici_bytes"])
        shares.append(100.0 * least / secs)
    return float(np.mean(shares)) if shares else None
