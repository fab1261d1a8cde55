"""`hbm_floor` for a configuration whose layers differ: the bytes a
decode step must move (the `work` module's `weight_bytes` once, and its
`decode_token_bytes` of the window's output tokens over the steps made)
at the chip's HBM bandwidth, over the measured device time of a step."""

import importlib

from benchmark.readers import program_time, window_contexts


def read(cap, *, work, program):
    step_ms = program_time.read(cap, program=program, per="step")
    steps = cap.decode_steps()
    if cap.peaks is None or step_ms is None or not steps:
        return None
    _, ctx = window_contexts.read(cap)
    w = importlib.import_module("benchmark.work." + work)
    need = w.weight_bytes(cap.config) \
        + w.decode_token_bytes(cap.config, ctx) / steps
    return 100.0 * (1e3 * need / cap.peaks["hbm_bytes_per_s"]) / step_ms
