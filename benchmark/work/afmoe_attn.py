"""Work of Trinity-Mini's decode attention, per decode step, in two
parts, one a kind of layer:

  `swa`: the window layers' walks over their rings (events `swa`):
  min(context, `sliding_window`) rows of K and V a slot and layer, 2,048
  B each, beside the slot's query rows in and outputs out; 4 x 32 x 128
  FLOPs a position.
  `full`: the full layers' paged walks (events `full`): every live
  context's K and V rows, once a layer.

The reader hands the contexts' SUM; the window layers' share is exact
where every live context is at least the window (this cell's prompts
are four windows long), and counts the mean context's window otherwise.
"""

from __future__ import annotations

from benchmark.work import afmoe_step as step


def _walks(m: dict, layers: int, keys: float, *, steps: float,
           rows_per_step: float) -> dict:
    s = step.sizes(m)
    qo = 2 * s["Hq"] * s["d"] * step.dtype_bytes(m)       # q in, o out
    return {"flops": steps * layers * step.attn_flops_per_key(m) * keys,
            "hbm_bytes": steps * layers * (step.kv_row_bytes(m) * keys
                                           + qo * rows_per_step),
            "ici_bytes": 0.0}


def swa(m: dict, tp: int, *, steps: float, kv_tokens_per_step: float,
        rows_per_step: float) -> dict:
    rows = max(rows_per_step, 1e-9)
    keys = min(kv_tokens_per_step / rows, step.sizes(m)["W"]) * rows
    return _walks(m, step.kinds(m)["swa"], keys, steps=steps,
                  rows_per_step=rows_per_step)


def full(m: dict, tp: int, *, steps: float, kv_tokens_per_step: float,
         rows_per_step: float) -> dict:
    return _walks(m, step.kinds(m)["full"], kv_tokens_per_step,
                  steps=steps, rows_per_step=rows_per_step)
