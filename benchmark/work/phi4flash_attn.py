"""Work of Phi-4-mini-flash's decode attention, all 16 attention layers,
per decode step: the full layer and the seven cross layers each read
the K and V of every live context (the one pool, eight times: the
layers run one after the other), the eight window layers the last
min(context, window) positions of their rings. Queries and outputs are
a few rows and are counted too.

The reader hands the contexts' SUM; the window layers' share is exact
where every live context is at least the window (this cell's prompts
are four windows long), and counts the mean context's window otherwise.
"""

from __future__ import annotations

from benchmark.work import phi4flash_step as step


def work(m: dict, tp: int, *, steps: float, kv_tokens_per_step: float,
         rows_per_step: float) -> dict:
    s, k = step.sizes(m), step.kinds(m)
    rows = max(rows_per_step, 1e-9)
    win = min(kv_tokens_per_step / rows, s["W"]) * rows
    keys = (k["full"] + k["cross"]) * kv_tokens_per_step + k["swa"] * win
    layers = k["full"] + k["cross"] + k["swa"]
    qo = 2 * s["Hq"] * s["hd"] * step.dtype_bytes(m)      # q in, o out
    return {"flops": steps * step.attn_flops_per_key(m) * keys,
            "hbm_bytes": steps * (step.kv_bytes_per_position(m) * keys
                                  + layers * qo * rows_per_step),
            "ici_bytes": 0.0}
