"""Work of learned sparse attention's decode step, all layers, per
decode step, in two parts:

  `index`: the indexer's scores and selection (events `sa_index` and
  `sa_topk`): every live context's index keys are read once a layer,
  128 B a position as published (whatever the pool pads them to),
  beside each slot's indexer queries in; 2 x 16 x 64 FLOPs a cached
  position. Selection has no least time of its own: what it costs
  reads as lost roofline.
  `attend`: the attention over the selected positions (events
  `sa_decode`): min(context, topk) rows of K and V a slot and layer,
  2,048 B each, beside the slot's query rows in and outputs out;
  4 x 32 x 128 FLOPs a selected position. A walk that fetches the whole
  context and masks shows as lost roofline, not as work.

The reader hands the step's mean context (`kv_tokens_per_step` /
`rows_per_step`): in this cell every context is above topk, so the
attended rows are topk a slot.
"""

from __future__ import annotations

from benchmark.work import keye_step as step


def index(m: dict, tp: int, *, steps: float, kv_tokens_per_step: float,
          rows_per_step: float) -> dict:
    s, item = step.sizes(m), step.dtype_bytes(m)
    L = m["num_hidden_layers"]
    q_in = (s["Hi"] * s["di"] + s["Hi"]) * item
    return {"flops": steps * L * step.index_flops_per_key(m)
            * kv_tokens_per_step,
            "hbm_bytes": steps * L * (
                step.index_row_bytes(m) * kv_tokens_per_step
                + q_in * rows_per_step),
            "ici_bytes": 0.0}


def attend(m: dict, tp: int, *, steps: float, kv_tokens_per_step: float,
           rows_per_step: float) -> dict:
    s, item = step.sizes(m), step.dtype_bytes(m)
    L = m["num_hidden_layers"]
    mean_ctx = kv_tokens_per_step / max(rows_per_step, 1e-9)
    keys = min(mean_ctx, s["topk"]) * rows_per_step
    qo = 2 * s["Hq"] * s["d"] * item
    return {"flops": steps * L * step.attn_flops_per_key(m) * keys,
            "hbm_bytes": steps * L * (step.kv_row_bytes(m) * keys
                                      + qo * rows_per_step),
            "ici_bytes": 0.0}
