"""Work of the row-parallel matmul + all-reduce (`w_o` and `w_down` of
every layer) per decode step on one chip of a TP group.

Each chip multiplies its [M, K/tp] activations by its [K/tp, D] slice
and the partial [M, D] results are summed over the chips. Least work
per chip: read the weight slice once, 2 M K D / tp FLOPs, and for the
sum a ring's 2 (tp - 1) / tp of the [M, D] result over the interconnect
(any all-reduce moves at least that per chip).
"""

from __future__ import annotations

from benchmark.work import model_step


def work(m: dict, tp: int, *, steps: float, rows_per_step: float,
         kv_tokens_per_step: float = 0.0) -> dict:
    """Same signature as every work function; the contexts' length does
    not enter a projection's work."""
    L = m["num_hidden_layers"]
    D, I = m["hidden_size"], m["intermediate_size"]
    hq = m["num_attention_heads"] * m["head_dim"]
    b = model_step.dtype_bytes(m)
    M = rows_per_step
    flops = hbm = ici = 0.0
    for K in (hq, I):                      # w_o, w_down
        flops += 2.0 * M * K * D / tp
        hbm += (K * D / tp + M * K / tp + M * D) * b
        ici += 2.0 * (tp - 1) / tp * M * D * b
    return {"flops": steps * L * flops, "hbm_bytes": steps * L * hbm,
            "ici_bytes": steps * L * ici}
