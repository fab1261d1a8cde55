"""Work of DeepSeek-V3's decode attention (the absorbed walk of the
latent pool, events `%mla_decode`), all layers, per decode step: every
live context's latent rows are read once a layer, 1,152 B a position as
published (whatever the pool pads them to: padding shows as lost
roofline, not as more work), beside each stream's query rows in
([H, rank + rope]) and latent outputs out ([H, rank]); 2 x (576 + 512)
x 128 FLOPs a cached position. At 128 heads the two bounds are within
1 % of each other (242 FLOP/B against the chip's 240).
"""

from __future__ import annotations

from benchmark.work import deepseek_step as step


def work(m: dict, tp: int, *, steps: float, kv_tokens_per_step: float,
         rows_per_step: float) -> dict:
    s = step.sizes(m)
    L, item = m["num_hidden_layers"], step.dtype_bytes(m)
    qo = s["H"] * (s["Rkv"] + s["rope"] + s["Rkv"]) * item
    return {"flops": steps * L * step.decode_attn_flops_per_key(m)
            * kv_tokens_per_step,
            "hbm_bytes": steps * L * (
                step.latent_row_bytes(m) * kv_tokens_per_step
                + qo * rows_per_step),
            "ici_bytes": 0.0}
