"""Operations and bytes of the model's step, from the configuration's
sizes alone (HF key names). Counted as the algorithm needs them: padded
rows, masked slots and recomputed work count nothing.

Per token, every layer multiplies by its four attention projections and
three MLP matrices (2 FLOPs per weight), and attends its context (QK^T
and PV: 2 x 2 x heads x head_dim FLOPs per key). The LM head is one
more matmul, paid once per token that is sampled. The embedding is a
gather and costs no FLOP.
"""

from __future__ import annotations


def layer_matmul_params(m: dict) -> int:
    D, I = m["hidden_size"], m["intermediate_size"]
    hq = m["num_attention_heads"] * m["head_dim"]
    hkv = m["num_key_value_heads"] * m["head_dim"]
    return D * hq + 2 * D * hkv + hq * D + 3 * D * I


def head_params(m: dict) -> int:
    return m["hidden_size"] * m["vocab_size"]


def attn_flops_per_key(m: dict) -> int:
    """FLOPs one query token spends per key it attends, in one layer."""
    return 4 * m["num_attention_heads"] * m["head_dim"]


def window_flops(m: dict, *, prefill_tokens: int, prefill_keys: int,
                 output_tokens: int, decode_keys: int) -> float:
    """Model FLOPs of a window: `prefill_tokens` prompt tokens really
    computed, attending `prefill_keys` keys in all (a whole prompt of n
    tokens attends n(n+1)/2); `output_tokens` sampled tokens, whose
    decode steps attended `decode_keys` keys in all."""
    L = m["num_hidden_layers"]
    through_layers = prefill_tokens + output_tokens
    return (2.0 * L * layer_matmul_params(m) * through_layers
            + 2.0 * head_params(m) * output_tokens
            + 1.0 * L * attn_flops_per_key(m) * (prefill_keys
                                                 + decode_keys))


def dtype_bytes(m: dict) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[m["torch_dtype"]]


def kv_bytes_per_token(m: dict) -> int:
    """K and V of one position, all layers, all KV heads."""
    return (2 * m["num_hidden_layers"] * m["num_key_value_heads"]
            * m["head_dim"] * dtype_bytes(m))


def weight_bytes_per_chip(m: dict, tp: int) -> float:
    """What one chip holds and a decode step reads once: its 1/tp of
    every layer's matmul weights, and the whole LM head (the program
    replicates it; tied or not, the step reads [D, V] once)."""
    b = dtype_bytes(m)
    return (m["num_hidden_layers"] * layer_matmul_params(m) * b / tp
            + head_params(m) * b)


def decode_step_bytes_per_chip(m: dict, tp: int, kv_tokens: float) -> float:
    """Bytes one decode step must read on one chip: its weights once and
    its share (KV heads are split over tp) of the live contexts' KV."""
    return weight_bytes_per_chip(m, tp) + kv_bytes_per_token(m) * kv_tokens / tp
