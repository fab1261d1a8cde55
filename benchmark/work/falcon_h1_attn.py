"""Work of Falcon-H1's decode attention walks (events `fh_attn`), per
decode step: every live context's K and V rows, 2,048 B a position,
once a layer, beside the slot's query rows in and outputs out; 4 x 20
x 128 FLOPs a position."""

from __future__ import annotations

from benchmark.work import falcon_h1_step as step


def work(m: dict, tp: int, *, steps: float, kv_tokens_per_step: float,
         rows_per_step: float) -> dict:
    s = step.sizes(m)
    qo = 2 * s["Hq"] * s["d"] * step.dtype_bytes(m)       # q in, o out
    return {"flops": steps * s["L"] * step.attn_flops_per_key(m)
            * kv_tokens_per_step,
            "hbm_bytes": steps * s["L"] * (step.kv_row_bytes(m)
                                           * kv_tokens_per_step
                                           + qo * rows_per_step),
            "ici_bytes": 0.0}
