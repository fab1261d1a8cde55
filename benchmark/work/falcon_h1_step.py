"""Operations and bytes of Falcon-H1's step on the one-chip stage the
configuration states, from the configuration's sizes alone (HF key
names). Counted as the algorithm needs them: a masked position, a page
fetched for the sake of one of its rows and recomputed work count
nothing.

Every layer has both mixers and the MLP:
  Mamba-2  W_in [D, 2 Ei + 2 G N + H] and W_out [Ei, D] (Ei =
           `mamba_d_ssm`); the depthwise conv, K taps over Ei + 2 G N
           channels; the recurrence, 5 FLOPs a state element a token
           (decay, input product, sum; output product, sum) over H x N
           x P elements; its float32 state and conv rows are read and
           written once a token.
  Attn     W_q, W_o [D, Hq d], W_k, W_v [D, Hkv d]; 4 x Hq x d FLOPs a
           position attended (10,240), beside its K and V rows, 2 x Hkv
           x d x 2 B = 2,048 B a layer.
  MLP      3 D I.
An admission runs every layer over the whole prompt but the last
layer's MLP, which only the prompt's last position goes through.
"""

from __future__ import annotations

import numpy as np


def sizes(m: dict) -> dict:
    return dict(
        D=m["hidden_size"], I=m["intermediate_size"],
        L=m["num_hidden_layers"], Hq=m["num_attention_heads"],
        Hkv=m["num_key_value_heads"], d=m["head_dim"], V=m["vocab_size"],
        H=m["mamba_n_heads"], P=m["mamba_d_head"], G=m["mamba_n_groups"],
        N=m["mamba_d_state"], K=m["mamba_d_conv"], Ei=m["mamba_d_ssm"])


def dtype_bytes(m: dict) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[m["torch_dtype"]]


def conv_channels(m: dict) -> int:
    s = sizes(m)
    return s["Ei"] + 2 * s["G"] * s["N"]


def mamba_params(m: dict) -> int:
    s = sizes(m)
    return s["D"] * (s["Ei"] + conv_channels(m) + s["H"]) + s["Ei"] * s["D"]


def attn_params(m: dict) -> int:
    s = sizes(m)
    return s["D"] * (2 * s["Hq"] + 2 * s["Hkv"]) * s["d"]


def mlp_params(m: dict) -> int:
    return 3 * m["hidden_size"] * m["intermediate_size"]


def head_params(m: dict) -> int:
    return m["hidden_size"] * m["vocab_size"]


def state_elements(m: dict) -> int:
    """SSD state elements of one slot and layer: H x N x P."""
    s = sizes(m)
    return s["H"] * s["N"] * s["P"]


def state_bytes_per_slot(m: dict) -> int:
    """A slot's float32 state over all layers: the SSD state and the
    conv's last K - 1 input rows."""
    s = sizes(m)
    return 4 * s["L"] * (state_elements(m)
                         + (s["K"] - 1) * conv_channels(m))


def kv_row_bytes(m: dict) -> int:
    """One cached position's K and V of ONE layer."""
    s = sizes(m)
    return 2 * s["Hkv"] * s["d"] * dtype_bytes(m)


def attn_flops_per_key(m: dict) -> int:
    s = sizes(m)
    return 4 * s["Hq"] * s["d"]


def layer_token_flops(m: dict) -> float:
    """A token through one layer, without attention's keys: the matmuls,
    the conv and the recurrence."""
    s = sizes(m)
    return (2.0 * (mamba_params(m) + attn_params(m) + mlp_params(m))
            + 2.0 * s["K"] * conv_channels(m) + 5.0 * state_elements(m))


def window_flops(m: dict, *, prompt_lens, contexts) -> float:
    """Model FLOPs of a window. prompt_lens: the length of each prompt
    admitted in it (row t attends t + 1 keys); contexts: for each output
    token received in it, the keys its step attended. A prompt's last
    position yields the first output token: the head is counted once an
    output token."""
    s = sizes(m)
    n = np.asarray(prompt_lens, np.float64)
    ctx = np.asarray(contexts, np.float64)
    per_key = attn_flops_per_key(m)
    prefill = (s["L"] * layer_token_flops(m) * n.sum()
               - 2.0 * mlp_params(m) * np.maximum(n - 1, 0).sum()
               + per_key * s["L"] * (n * (n + 1) / 2).sum())
    decode = (s["L"] * layer_token_flops(m) + 2.0 * head_params(m)) \
        * ctx.size + per_key * s["L"] * ctx.sum()
    return float(prefill + decode)


def weight_bytes(m: dict) -> float:
    """What a decode step reads once: every layer's matmul weights and
    the head."""
    s = sizes(m)
    return float((s["L"] * (mamba_params(m) + attn_params(m)
                            + mlp_params(m)) + head_params(m))
                 * dtype_bytes(m))


def decode_token_bytes(m: dict, contexts) -> float:
    """Bytes the decode steps of these output tokens must move beside
    the weights, summed: every layer's K and V rows of the context, and
    the slot's state read and written."""
    ctx = np.asarray(contexts, np.float64)
    return float(kv_row_bytes(m) * sizes(m)["L"] * ctx.sum()
                 + 2.0 * state_bytes_per_slot(m) * ctx.size)
