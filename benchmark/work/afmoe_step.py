"""Operations and bytes of Trinity-Mini's step as ONE CHIP of the
deployment the configuration states computes it, from the
configuration's sizes alone (HF key names, plus the file's
`deployment`). Counted as the algorithm needs them: a masked position,
a page fetched for the sake of one of its rows and recomputed work
count nothing.

Every layer: gated GQA attention (W_q, W_k, W_v, W_g, W_o). The first
`num_dense_layers` layers: a SwiGLU at `intermediate_size`. Every other
layer: the shared expert whole, the router over all
`deployment.routed_experts_total` experts, and the routed pairs that
land on the `num_experts` experts this chip holds: on average k x held /
total of a token's k, 1 of 8 here, NOT 8. What the other chips of the
layer compute is theirs. A decode step reads the weights of the held
experts its pairs reach, not of all sixteen (`experts_touched`).

Attention per query and layer, 2 FLOPs a multiply-add: 4 x Hq x d a
position attended (16,384), beside its 2 x Hkv x d x 2 B of K and V
(2,048 B). A window layer (`layer_types` "sliding_attention") attends
min(context, `sliding_window`) positions, a full layer the context.
"""

from __future__ import annotations

import numpy as np


def sizes(m: dict) -> dict:
    return dict(
        D=m["hidden_size"], F=m["moe_intermediate_size"],
        Hq=m["num_attention_heads"], Hkv=m["num_key_value_heads"],
        d=m["head_dim"], W=m["sliding_window"],
        E=m["deployment"]["routed_experts_total"], held=m["num_experts"],
        k=m["num_experts_per_tok"])


def kinds(m: dict) -> dict:
    """How many layers of each kind the stack has."""
    types = m["layer_types"][:m["num_hidden_layers"]]
    return {"swa": types.count("sliding_attention"),
            "full": types.count("full_attention"),
            "dense": m["num_dense_layers"],
            "moe": m["num_hidden_layers"] - m["num_dense_layers"]}


def dtype_bytes(m: dict) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[m["torch_dtype"]]


def attn_params(m: dict) -> int:
    """W_q, W_g, W_o of Hq x d columns or rows each, W_k and W_v."""
    s = sizes(m)
    return s["D"] * (3 * s["Hq"] + 2 * s["Hkv"]) * s["d"]


def dense_params(m: dict) -> int:
    return 3 * m["hidden_size"] * m["intermediate_size"]


def expert_params(m: dict) -> int:
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def shared_params(m: dict) -> int:
    return m["num_shared_experts"] * expert_params(m)


def router_params(m: dict) -> int:
    return m["hidden_size"] * sizes(m)["E"]


def head_params(m: dict) -> int:
    return m["hidden_size"] * m["vocab_size"]


def held_pairs_per_token(m: dict) -> float:
    """Routed pairs of one token and expert layer that land on this
    chip, on average: k x held / total."""
    s = sizes(m)
    return s["k"] * s["held"] / s["E"]


def experts_touched(m: dict, rows: float) -> float:
    """Held experts whose weights a step of `rows` tokens reads in one
    expert layer, on average: rows x k x held / total pairs land here,
    each on one of the held experts as an even routing throws them, and
    the ragged grouped GEMM fetches nothing for an expert without a
    pair: held x (1 - (1 - 1 / held) ** pairs): 15.74 of 16 at 64
    rows."""
    s = sizes(m)
    pairs = rows * held_pairs_per_token(m)
    return s["held"] * (1.0 - (1.0 - 1.0 / s["held"]) ** pairs)


def kv_row_bytes(m: dict) -> int:
    """One cached position's K and V of ONE layer."""
    s = sizes(m)
    return 2 * s["Hkv"] * s["d"] * dtype_bytes(m)


def attn_flops_per_key(m: dict) -> int:
    s = sizes(m)
    return 4 * s["Hq"] * s["d"]


def in_window(m: dict, contexts):
    """Of each context, the positions a window layer's step attends."""
    return np.minimum(np.asarray(contexts, np.float64), sizes(m)["W"])


def stack_params(m: dict, experts: float) -> float:
    """Matmul parameters of all layers with `experts` routed experts
    counted a layer (the held ones, or those a step reads)."""
    k = kinds(m)
    return (m["num_hidden_layers"] * attn_params(m)
            + k["dense"] * dense_params(m)
            + k["moe"] * (shared_params(m) + router_params(m)
                          + experts * expert_params(m)))


def token_flops(m: dict) -> float:
    """A token through all layers as this chip computes it, without
    attention's keys and without the head."""
    return 2.0 * stack_params(m, held_pairs_per_token(m))


def window_flops(m: dict, *, prompt_lens, contexts) -> float:
    """Model FLOPs of a window on this chip. prompt_lens: the length of
    each prompt admitted in it (row t attends t + 1 keys in a full
    layer, min(t + 1, W) in a window layer); contexts: for each output
    token received in it, the keys its step's full layers read. A
    prompt's last position yields the first output token: the head is
    counted once an output token."""
    k, W = kinds(m), sizes(m)["W"]
    n = np.asarray(prompt_lens, np.float64)
    ctx = np.asarray(contexts, np.float64)
    tri = lambda a: a * (a + 1) / 2  # noqa: E731
    win = np.where(n <= W, tri(n), tri(W) + (n - W) * W)
    per_key = attn_flops_per_key(m)
    prefill = token_flops(m) * n.sum() + per_key * (
        k["full"] * tri(n).sum() + k["swa"] * win.sum())
    decode = (token_flops(m) + 2.0 * head_params(m)) * ctx.size \
        + per_key * (k["full"] * ctx.sum()
                     + k["swa"] * in_window(m, ctx).sum())
    return float(prefill + decode)


def weight_bytes(m: dict) -> float:
    """What a decode step reads once on this chip: every layer's matmul
    weights, of the held experts those a step's pairs reach
    (`experts_touched` at the server's batch: every slot's row flows
    through a step, live or not), and the head."""
    rows = m["server"]["batch"]
    return float((stack_params(m, experts_touched(m, rows))
                  + head_params(m)) * dtype_bytes(m))


def decode_token_bytes(m: dict, contexts) -> float:
    """Bytes the decode steps of these output tokens must move beside
    the weights, summed: the K and V rows of the context in every full
    layer and of min(context, window) positions in every window
    layer."""
    k = kinds(m)
    ctx = np.asarray(contexts, np.float64)
    return float(kv_row_bytes(m) * (k["full"] * ctx.sum()
                                    + k["swa"] * in_window(m, ctx).sum()))
