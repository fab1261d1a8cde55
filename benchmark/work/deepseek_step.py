"""Operations and bytes of DeepSeek-V3's step as ONE CHIP of the
deployment the configuration states computes it, from the
configuration's sizes alone (HF key names, plus the file's
`deployment`). Counted as the algorithm needs them: a padded row or
lane, a masked slot and recomputed work count nothing.

Layers: `first_k_dense_replace` dense ones (MLA + SwiGLU of
`intermediate_size`), the rest expert layers (MLA + the shared expert
whole + the router over all `deployment.routed_experts_total` experts +
the routed pairs that land on the `n_routed_experts` experts this chip
holds: on average k x held / total of a token's k, 0.5 of 8 here, NOT
8). What the other chips of the layer compute is theirs.

Attention per key and layer, 2 FLOPs a multiply-add, H heads:
  decode (absorbed, the form a latent cache is read in): a score over
  the row's kv_lora_rank + rope values and a value sum over its
  kv_lora_rank: 2 x (576 + 512) x 128 = 278,528;
  a prompt (expanded): 2 x (nope + rope + v) x H = 81,920.
The absorbed products q_nope W_uk and o_lat W_uv cost a decode token
what c_kv W_kvb costs a prompt token (the same parameters), so the
projections count alike in both.
"""

from __future__ import annotations

import numpy as np


def sizes(m: dict) -> dict:
    dep = m["deployment"]
    return dict(
        D=m["hidden_size"], I=m["intermediate_size"],
        F=m["moe_intermediate_size"], L=m["num_hidden_layers"],
        dense=m["first_k_dense_replace"], H=m["num_attention_heads"],
        Rq=m["q_lora_rank"], Rkv=m["kv_lora_rank"],
        nope=m["qk_nope_head_dim"], rope=m["qk_rope_head_dim"],
        vd=m["v_head_dim"], E=dep["routed_experts_total"],
        held=m["n_routed_experts"], shared=m["n_shared_experts"],
        k=m["num_experts_per_tok"], V=m["vocab_size"])


def kinds(m: dict) -> dict:
    s = sizes(m)
    return dict(dense=s["dense"], moe=s["L"] - s["dense"])


def mla_params(m: dict) -> int:
    s = sizes(m)
    return (s["D"] * s["Rq"] + s["Rq"] * s["H"] * (s["nope"] + s["rope"])
            + s["D"] * (s["Rkv"] + s["rope"])
            + s["Rkv"] * s["H"] * (s["nope"] + s["vd"])
            + s["H"] * s["vd"] * s["D"])


def dense_mlp_params(m: dict) -> int:
    return 3 * m["hidden_size"] * m["intermediate_size"]


def expert_params(m: dict) -> int:
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def shared_params(m: dict) -> int:
    return m["n_shared_experts"] * expert_params(m)


def router_params(m: dict) -> int:
    return m["hidden_size"] * sizes(m)["E"]


def head_params(m: dict) -> int:
    return m["hidden_size"] * m["vocab_size"]


def held_pairs_per_token(m: dict) -> float:
    """Routed pairs of one token and layer that land on this chip, on
    average: k x held / total."""
    s = sizes(m)
    return s["k"] * s["held"] / s["E"]


def dtype_bytes(m: dict) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[m["torch_dtype"]]


def latent_row_bytes(m: dict) -> int:
    """One cached position of ONE layer: [c_kv | k_pe], as published."""
    s = sizes(m)
    return (s["Rkv"] + s["rope"]) * dtype_bytes(m)


def decode_attn_flops_per_key(m: dict) -> int:
    s = sizes(m)
    return 2 * (s["Rkv"] + s["rope"] + s["Rkv"]) * s["H"]


def prefill_attn_flops_per_key(m: dict) -> int:
    s = sizes(m)
    return 2 * (s["nope"] + s["rope"] + s["vd"]) * s["H"]


def layer_params(m: dict) -> dict:
    """Matmul parameters ON THIS CHIP of one layer of each kind."""
    s = sizes(m)
    return dict(
        dense=mla_params(m) + dense_mlp_params(m),
        moe=mla_params(m) + shared_params(m) + router_params(m)
        + s["held"] * expert_params(m))


def token_flops(m: dict) -> float:
    """A token through all layers as this chip computes it, without
    attention's keys and without the head."""
    k = kinds(m)
    per_moe = (mla_params(m) + shared_params(m) + router_params(m)
               + held_pairs_per_token(m) * expert_params(m))
    return 2.0 * (k["dense"] * (mla_params(m) + dense_mlp_params(m))
                  + k["moe"] * per_moe)


def window_flops(m: dict, *, prompt_lens, contexts) -> float:
    """Model FLOPs of a window on this chip. prompt_lens: the length of
    each prompt admitted in it (expanded causal attention over itself);
    contexts: for each output token received in it, the keys its step
    attended (absorbed). A prompt's last position yields the first
    output token: the head is counted once an output token."""
    L = m["num_hidden_layers"]
    n = np.asarray(prompt_lens, np.float64)
    ctx = np.asarray(contexts, np.float64)
    prefill = (token_flops(m) * n.sum()
               + prefill_attn_flops_per_key(m) * L
               * (n * (n + 1) / 2).sum())
    decode = ((token_flops(m) + 2.0 * head_params(m)) * ctx.size
              + decode_attn_flops_per_key(m) * L * ctx.sum())
    return float(prefill + decode)


def weight_bytes(m: dict) -> float:
    """What a decode step reads once on this chip: every layer's matmul
    weights, ALL the held experts among them (at 128 slots an expert
    without a pair in a step is rare: (15/16)^64), and the head."""
    k, lp = kinds(m), layer_params(m)
    params = k["dense"] * lp["dense"] + k["moe"] * lp["moe"] \
        + head_params(m)
    return float(params * dtype_bytes(m))


def decode_token_bytes(m: dict, contexts) -> float:
    """Bytes the decode steps of these output tokens must move beside
    the weights, summed: every layer's latent rows of the context."""
    ctx = np.asarray(contexts, np.float64)
    return float(m["num_hidden_layers"] * latent_row_bytes(m) * ctx.sum())
