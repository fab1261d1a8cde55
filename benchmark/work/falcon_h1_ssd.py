"""Work of Falcon-H1's two SSD kernels (kernels/ssd.py), every layer:

  `work` (decode, `ssd_step`, per decode step): every row's [H, N, P]
  float32 state read and written, beside the step's x, B, C, dt rows in
  and its y row out (float32); 5 FLOPs a state element (decay, input
  product and sum; output product and sum). The small matmuls around
  it, the conv and the gate are XLA's and not this kernel's.
  `scan_work` (admission, `ssd_scan`, per prompt): chunks of L =
  `mamba_chunk_size` positions; a chunk and group's C B^T [L, L] over N,
  a head's masked [L, L] by [L, P] product, its C S [L, N] by [N, P]
  and its B^T (w u) [N, L] by [L, P], 2 FLOPs a multiply-add; the
  prompt's rows in and out once and the state written once.
"""

from __future__ import annotations

from benchmark.work import falcon_h1_step as step


def _row_bytes(m: dict) -> int:
    """x, B, C, dt of one token and layer in, y out, float32."""
    s = step.sizes(m)
    return 4 * (2 * s["H"] * s["P"] + 2 * s["G"] * s["N"] + s["H"])


def work(m: dict, tp: int, *, steps: float, rows_per_step: float,
         kv_tokens_per_step: float = 0.0) -> dict:
    L = step.sizes(m)["L"]
    el = step.state_elements(m)
    return {"flops": steps * L * 5.0 * el * rows_per_step,
            "hbm_bytes": steps * L * rows_per_step
            * (2 * 4 * el + _row_bytes(m)),
            "ici_bytes": 0.0}


def scan_work(m: dict, prompt_len: int) -> dict:
    s = step.sizes(m)
    C = int(m["mamba_chunk_size"])
    chunks = -(-prompt_len // C)
    per_chunk = (2.0 * s["G"] * C * C * s["N"]
                 + s["H"] * (2.0 * C * C * s["P"]
                             + 4.0 * C * s["N"] * s["P"]))
    return {"flops": s["L"] * chunks * per_chunk,
            "hbm_bytes": s["L"] * (prompt_len * _row_bytes(m)
                                   + 4 * step.state_elements(m)),
            "ici_bytes": 0.0}
