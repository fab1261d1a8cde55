"""Work of the decode attention (one new token per live slot over its
paged context), per decode step and chip, all layers: it must read the
K and V of every live context once, and spends 4 x heads x head_dim
FLOPs per key. Queries and outputs are a few rows and are counted too.
"""

from __future__ import annotations

from benchmark.work import model_step


def work(m: dict, tp: int, *, steps: float, kv_tokens_per_step: float,
         rows_per_step: float) -> dict:
    """`steps` decode steps, each attending `kv_tokens_per_step` cached
    positions in all (summed over the live slots) for `rows_per_step`
    live slots. Returns FLOPs and HBM bytes on one chip."""
    L = m["num_hidden_layers"]
    b = model_step.dtype_bytes(m)
    qo = 2 * m["num_attention_heads"] * m["head_dim"] * b   # q in, o out
    flops = steps * L * model_step.attn_flops_per_key(m) \
        * kv_tokens_per_step / tp
    hbm = steps * (model_step.kv_bytes_per_token(m) * kv_tokens_per_step
                   + L * qo * rows_per_step) / tp
    return {"flops": flops, "hbm_bytes": hbm, "ici_bytes": 0.0}
