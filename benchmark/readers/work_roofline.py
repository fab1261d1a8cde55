"""A piece of work's share of its roofline: the least time the chip
could take for the work done in the traced slice (a function under
`benchmark/work/`, at the chip's peaks) over the summed device time of
the trace events that implement the work today. `patterns` name those
events, counted inside the executions of `program` (see
`trace_reduce.executions`), whose whole executions also count the
decode steps the slice made."""

import importlib

import numpy as np

from benchmark import peaks, trace_reduce


def read(cap, *, work, patterns, program):
    if cap.trace is None or cap.peaks is None:
        return None
    steps_w = cap.decode_steps()
    out_tokens, decode_keys = cap.window_tokens()
    if not steps_w or not out_tokens:
        return None
    fn = importlib.import_module("benchmark.work." + work).work
    shares = []
    for dev in cap.trace.devices:
        spans = trace_reduce.whole_executions(cap.trace, dev, program)
        secs, hits = trace_reduce.pattern_seconds(
            trace_reduce.ops_inside(dev, spans), patterns)
        if not spans or not hits or secs <= 0:
            continue
        w = fn(cap.config, cap.chips, steps=len(spans) * cap.chunk,
               rows_per_step=out_tokens / steps_w,
               kv_tokens_per_step=decode_keys / steps_w)
        least, _ = peaks.least_seconds(
            w["flops"], w["hbm_bytes"], cap.peaks,
            dtype=cap.config["torch_dtype"], ici_bytes=w["ici_bytes"])
        shares.append(100.0 * least / secs)
    return float(np.mean(shares)) if shares else None
