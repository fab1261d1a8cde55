"""Work of the routed experts' two grouped GEMMs on this chip (events
`%moe_gmm`), all expert layers, per decode step: the matrices (3 x D x F
each: 2048 x 1024) of the held experts that the step's pairs reach
(`afmoe_step.experts_touched`: 15.74 of 16 at 64 rows; an expert without
a pair is not fetched) are read once a step and layer, beside the rows
of the pairs that landed here (a row of D in, of F out and in again, of
D out); 6 x D x F FLOPs a pair. Pairs a step: rows x k x held / total,
the share an even routing gives this chip. The shared expert is not a
grouped GEMM and is not counted here.
"""

from __future__ import annotations

from benchmark.work import afmoe_step as step


def work(m: dict, tp: int, *, steps: float, rows_per_step: float,
         kv_tokens_per_step: float = 0.0) -> dict:
    s, item = step.sizes(m), step.dtype_bytes(m)
    layers = step.kinds(m)["moe"]
    pairs = rows_per_step * step.held_pairs_per_token(m)
    per_pair = 2 * (s["D"] + s["F"]) * item
    return {"flops": steps * layers * 2.0 * step.expert_params(m) * pairs,
            "hbm_bytes": steps * layers * (
                step.experts_touched(m, rows_per_step)
                * step.expert_params(m) * item
                + per_pair * pairs),
            "ici_bytes": 0.0}
