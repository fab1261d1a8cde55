"""The whole step's share of the chips' peak: model FLOPs of the tokens
processed in the window (prompt tokens really prefilled and output
tokens, from `work/model_step.py`) over window x chips x peak FLOP/s."""

from benchmark.work import model_step


def read(cap):
    if cap.peaks is None:
        return None
    m = cap.config
    out_tokens, decode_keys = cap.window_tokens()
    pre_tokens, pre_keys = cap.window_prefill()
    if not out_tokens:
        return None
    flops = model_step.window_flops(
        m, prefill_tokens=pre_tokens, prefill_keys=pre_keys,
        output_tokens=out_tokens, decode_keys=decode_keys)
    peak = cap.peaks["flops_per_s"][m["torch_dtype"]]
    return 100.0 * flops / (cap.seconds * cap.chips * peak)
