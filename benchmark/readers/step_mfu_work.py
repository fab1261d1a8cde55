"""`step_mfu` for a configuration whose layers differ: the model FLOPs
of the window from the `work` module's own `window_flops(m,
prompt_lens=, contexts=)` over window x chips x peak FLOP/s."""

import importlib

from benchmark.readers import window_contexts


def read(cap, *, work):
    if cap.peaks is None:
        return None
    prompts, ctx = window_contexts.read(cap)
    if not ctx.size:
        return None
    fn = importlib.import_module("benchmark.work." + work).window_flops
    peak = cap.peaks["flops_per_s"][cap.config["torch_dtype"]]
    return 100.0 * fn(cap.config, prompt_lens=prompts, contexts=ctx) / (
        cap.seconds * cap.chips * peak)
