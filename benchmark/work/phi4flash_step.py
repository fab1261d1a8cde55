"""Operations and bytes of Phi-4-mini-flash's step, per kind of layer,
from the configuration's sizes alone (HF key names, plus the file's
`assumed` for the state-space sizes). Counted as the algorithm needs
them: a padded row, a masked slot, the zero half of a padded query and
recomputed work count nothing.

Layers (L = 32, half = 16): mamba l even <= half; swa l odd < half;
full l = half + 1; cross l odd > half + 1; gmu l even > half. Every
layer has the SwiGLU MLP (3 D I weights).

An admission runs layers 0..half and layer half+1's K/V projection over
the whole prompt, and everything after that on one position (the
program's shortcut: those layers write no state): a prompt token is
counted through that much and no more.

Differential attention, per query-head pair and key: two softmaxes,
each a 64-wide QK and a 128-wide PV, 2 FLOPs a multiply-add:
2 x (2 x 64 + 2 x 128) = 768; 20 pairs: 15,360 a key a layer.
"""

from __future__ import annotations

import numpy as np


def sizes(m: dict) -> dict:
    a = m["assumed"]
    D, Hq = m["hidden_size"], m["num_attention_heads"]
    return dict(D=D, I=m["intermediate_size"], L=m["num_hidden_layers"],
                Hq=Hq, Hkv=m["num_key_value_heads"], hd=D // Hq,
                V=m["vocab_size"], W=m["sliding_window"],
                N=a["mamba_d_state"], K=a["mamba_d_conv"],
                E=a["mamba_expand"] * D, R=a["mamba_dt_rank"])


def kinds(m: dict) -> dict:
    """How many layers of each kind."""
    L = m["num_hidden_layers"]
    half = L // 2
    out = dict(mamba=0, swa=0, full=0, cross=0, gmu=0)
    for li in range(L):
        if li % 2 == 0:
            out["mamba" if li <= half else "gmu"] += 1
        elif li < half:
            out["swa"] += 1
        else:
            out["full" if li == half + 1 else "cross"] += 1
    return out


def mix_params(m: dict) -> dict:
    """Matmul weights of each kind's mixer (the MLP is `mlp_params`)."""
    s = sizes(m)
    D, E, N, R = s["D"], s["E"], s["N"], s["R"]
    nq, nkv = s["Hq"] * s["hd"], s["Hkv"] * s["hd"]
    return dict(
        mamba=D * 2 * E + E * (R + 2 * N) + R * E + E * D,
        swa=D * (nq + 2 * nkv) + nq * D,
        full=D * (nq + 2 * nkv) + nq * D,
        cross=D * nq + nq * D,
        gmu=D * E + E * D)


def mlp_params(m: dict) -> int:
    return 3 * m["hidden_size"] * m["intermediate_size"]


def head_params(m: dict) -> int:
    return m["hidden_size"] * m["vocab_size"]


def scan_flops_per_token(m: dict) -> int:
    """One state-space layer's recurrence for one token, beside its
    matmuls: per (channel, state) the decay's multiply, the input's two
    multiplies, the add, and the output's multiply-add: 6; and the
    depthwise convolution's K multiply-adds a channel."""
    s = sizes(m)
    return 6 * s["E"] * s["N"] + 2 * s["K"] * s["E"]


def attn_flops_per_key(m: dict) -> int:
    s = sizes(m)
    return (s["Hq"] // 2) * 2 * (2 * s["hd"] + 2 * 2 * s["hd"])


def dtype_bytes(m: dict) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[m["torch_dtype"]]


def kv_bytes_per_position(m: dict) -> int:
    """K and V of one position in ONE attention layer."""
    s = sizes(m)
    return 2 * s["Hkv"] * s["hd"] * dtype_bytes(m)


def state_bytes_per_slot(m: dict) -> int:
    """Conv tail and SSM state of one slot, all state-space layers
    (float32)."""
    s = sizes(m)
    return kinds(m)["mamba"] * (s["K"] - 1 + s["N"]) * s["E"] * 4


def token_flops(m: dict) -> float:
    """A decode token through all layers, without attention's keys and
    without the head."""
    k, mp = kinds(m), mix_params(m)
    return (2.0 * sum(k[x] * mp[x] for x in k)
            + 2.0 * m["num_hidden_layers"] * mlp_params(m)
            + k["mamba"] * scan_flops_per_token(m))


def prompt_token_flops(m: dict) -> float:
    """A prompt token through what an admission computes for every
    position: the mamba and swa layers with their MLPs, and layer
    half+1's K/V projection."""
    s, k, mp = sizes(m), kinds(m), mix_params(m)
    through = k["mamba"] + k["swa"]
    return (2.0 * (k["mamba"] * mp["mamba"] + k["swa"] * mp["swa"])
            + 2.0 * through * mlp_params(m)
            + k["mamba"] * scan_flops_per_token(m)
            + 2.0 * s["D"] * 2 * s["Hkv"] * s["hd"])


def window_flops(m: dict, *, prompt_lens, contexts) -> float:
    """Model FLOPs of a window. prompt_lens: the length of each prompt
    admitted in it; contexts: for each output token received in it, the
    keys its step attended in a full layer (prompt + tokens before it).
    The prompt's last position goes on through the rest of the model and
    yields the first output token: its decode-shaped work is counted
    with the output tokens (one of `contexts` a request)."""
    s, k = sizes(m), kinds(m)
    W = s["W"]
    n = np.asarray(prompt_lens, np.float64)
    ctx = np.asarray(contexts, np.float64)
    # a window layer's keys over a whole prompt: position p sees
    # min(p + 1, W)
    full_part = np.minimum(n, W)
    swa_keys = full_part * (full_part + 1) / 2 + np.maximum(n - W, 0) * W
    apk = attn_flops_per_key(m)
    prefill = (prompt_token_flops(m) * n.sum()
               + apk * k["swa"] * swa_keys.sum())
    decode = ((token_flops(m) + 2.0 * head_params(m)) * ctx.size
              + apk * ((k["full"] + k["cross"]) * ctx.sum()
                       + k["swa"] * np.minimum(ctx, W).sum()))
    return float(prefill + decode)


def weight_bytes(m: dict) -> float:
    """What a decode step reads once: every layer's matmul weights and
    the head (tied: one [D, V] read)."""
    k, mp = kinds(m), mix_params(m)
    params = (sum(k[x] * mp[x] for x in k)
              + m["num_hidden_layers"] * mlp_params(m) + head_params(m))
    return float(params * dtype_bytes(m))


def decode_token_bytes(m: dict, contexts) -> float:
    """Bytes the decode steps of these output tokens must move beside
    the weights, summed: layer half+1's K/V once for it and once for
    every cross layer, each window layer min(context, W) positions, and
    the slot's state read and written."""
    s, k = sizes(m), kinds(m)
    ctx = np.asarray(contexts, np.float64)
    kvb = kv_bytes_per_position(m)
    return float(kvb * ((k["full"] + k["cross"]) * ctx.sum()
                        + k["swa"] * np.minimum(ctx, s["W"]).sum())
                 + 2.0 * state_bytes_per_slot(m) * ctx.size)
