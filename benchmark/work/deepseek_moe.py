"""Work of the routed experts' two grouped GEMMs on this chip (events
`%moe_gmm`), all expert layers, per decode step: the held experts'
matrices (3 x D x F each) are read once a step and layer, beside the
rows of the pairs that landed here (a row of D in, of F out and in
again, of D out); 6 x D x F FLOPs a pair. Pairs a step: rows x k x
held / total, the share an even routing gives this chip.
"""

from __future__ import annotations

from benchmark.work import deepseek_step as step


def work(m: dict, tp: int, *, steps: float, rows_per_step: float,
         kv_tokens_per_step: float = 0.0) -> dict:
    s, item = step.sizes(m), step.dtype_bytes(m)
    layers = step.kinds(m)["moe"]
    pairs = rows_per_step * step.held_pairs_per_token(m)
    per_pair = 2 * (s["D"] + s["F"]) * item
    return {"flops": steps * layers * 2.0 * step.expert_params(m) * pairs,
            "hbm_bytes": steps * layers * (
                s["held"] * step.expert_params(m) * item
                + per_pair * pairs),
            "ici_bytes": 0.0}
