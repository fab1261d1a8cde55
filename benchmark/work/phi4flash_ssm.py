"""Work of the one-token state update (`ssm_step`) of the nine
state-space layers, per decode step: every live slot's SSM state is
read and written (float32), beside the step's few rows of inputs (x, dt,
B, C) and its output; 6 FLOPs a (channel, state). The small matmuls
around it and the convolution are XLA's and are not this kernel's.
"""

from __future__ import annotations

from benchmark.work import phi4flash_step as step


def work(m: dict, tp: int, *, steps: float, rows_per_step: float,
         kv_tokens_per_step: float = 0.0) -> dict:
    s, k = step.sizes(m), step.kinds(m)
    per_row = 2 * s["N"] * s["E"] * 4 + (3 * s["E"] + 2 * s["N"]) * 4
    return {"flops": steps * k["mamba"] * 6 * s["E"] * s["N"]
            * rows_per_step,
            "hbm_bytes": steps * k["mamba"] * per_row * rows_per_step,
            "ici_bytes": 0.0}
