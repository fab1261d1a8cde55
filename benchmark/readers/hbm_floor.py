"""Decode is bandwidth-bound: the bytes a token step must read on one
chip (its weights once, its share of the live contexts' KV) at the
chip's HBM bandwidth, over the measured device time of a step."""

from benchmark.readers import program_time
from benchmark.work import model_step


def read(cap, *, program):
    step_ms = program_time.read(cap, program=program, per="step")
    steps = cap.decode_steps()
    if cap.peaks is None or step_ms is None or not steps:
        return None
    _, decode_keys = cap.window_tokens()
    need = model_step.decode_step_bytes_per_chip(
        cap.config, cap.chips, decode_keys / steps)
    floor_ms = 1e3 * need / cap.peaks["hbm_bytes_per_s"]
    return 100.0 * floor_ms / step_ms
