"""Operations and bytes of the step of Keye-VL-2.0's language model as
ONE CHIP of the deployment the configuration states computes it, from
the configuration's sizes alone (HF key names, plus the file's
`deployment`). Counted as the algorithm needs them: a padded lane, a
masked position, a page fetched for the sake of one of its rows and
recomputed work count nothing.

Every layer: GQA attention with QK-norm (W_q, W_k, W_v, W_o), the
indexer (W_qI, W_kI, W_w), the router over all
`deployment.routed_experts_total` experts, and the routed pairs that
land on the `num_experts` experts this chip holds: on average k x held /
total of a token's k, 1 of 8 here, NOT 8. What the other chips of the
layer compute is theirs. A decode step reads the weights of the held
experts its pairs reach, not of all sixteen (`experts_touched`).

Attention per query and layer, 2 FLOPs a multiply-add:
  index scores over the WHOLE context: 2 x Hi x di a cached position
  (16 x 64: 2,048), beside its 128 B of index key;
  scores and values over the SELECTED positions only, min(context,
  topk): 4 x Hq x d a selected position (16,384), beside its 2 x Hkv x d
  x 2 B of K and V (2,048 B).
"""

from __future__ import annotations

import numpy as np


def sizes(m: dict) -> dict:
    dep, sa = m["deployment"], m["sa_config"]
    return dict(
        D=m["hidden_size"], F=m["moe_intermediate_size"],
        L=m["num_hidden_layers"], Hq=m["num_attention_heads"],
        Hkv=m["num_key_value_heads"], d=m["head_dim"],
        Hi=sa["indexer_num_heads"], di=sa["indexer_head_dim"],
        topk=sa["topk"], E=dep["routed_experts_total"],
        held=m["num_experts"], k=m["num_experts_per_tok"],
        V=m["vocab_size"])


def dtype_bytes(m: dict) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[m["torch_dtype"]]


def attn_params(m: dict) -> int:
    s = sizes(m)
    return s["D"] * (2 * s["Hq"] + 2 * s["Hkv"]) * s["d"]


def indexer_params(m: dict) -> int:
    s = sizes(m)
    return s["D"] * (s["Hi"] * s["di"] + s["di"] + s["Hi"])


def router_params(m: dict) -> int:
    return m["hidden_size"] * sizes(m)["E"]


def expert_params(m: dict) -> int:
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def head_params(m: dict) -> int:
    return m["hidden_size"] * m["vocab_size"]


def held_pairs_per_token(m: dict) -> float:
    """Routed pairs of one token and layer that land on this chip, on
    average: k x held / total."""
    s = sizes(m)
    return s["k"] * s["held"] / s["E"]


def experts_touched(m: dict, rows: float) -> float:
    """Held experts whose weights a step of `rows` tokens reads, on
    average: rows x k x held / total pairs land here, each on one of the
    held experts as an even routing throws them, and the ragged grouped
    GEMM fetches nothing for an expert without a pair: held x (1 - (1 -
    1 / held) ** pairs). At 32 rows that is 13.97 of 16 (two pairs an
    expert leave one expert in eight without any); the first chip run
    read 104 % of the roofline with all 16 counted."""
    s = sizes(m)
    pairs = rows * held_pairs_per_token(m)
    return s["held"] * (1.0 - (1.0 - 1.0 / s["held"]) ** pairs)


def index_row_bytes(m: dict) -> int:
    """One cached position's index key of ONE layer, as published."""
    return sizes(m)["di"] * dtype_bytes(m)


def kv_row_bytes(m: dict) -> int:
    """One cached position's K and V of ONE layer."""
    s = sizes(m)
    return 2 * s["Hkv"] * s["d"] * dtype_bytes(m)


def index_flops_per_key(m: dict) -> int:
    s = sizes(m)
    return 2 * s["Hi"] * s["di"]


def attn_flops_per_key(m: dict) -> int:
    s = sizes(m)
    return 4 * s["Hq"] * s["d"]


def attended(m: dict, contexts):
    """Of each context, the positions its step attends."""
    return np.minimum(np.asarray(contexts, np.float64), sizes(m)["topk"])


def layer_params(m: dict) -> int:
    """Matmul parameters ON THIS CHIP of one layer."""
    return (attn_params(m) + indexer_params(m) + router_params(m)
            + sizes(m)["held"] * expert_params(m))


def layer_params_read(m: dict, rows: float) -> float:
    """Of `layer_params`, what a step of `rows` tokens reads."""
    return (attn_params(m) + indexer_params(m) + router_params(m)
            + experts_touched(m, rows) * expert_params(m))


def token_flops(m: dict) -> float:
    """A token through all layers as this chip computes it, without
    attention's keys and without the head."""
    per = (attn_params(m) + indexer_params(m) + router_params(m)
           + held_pairs_per_token(m) * expert_params(m))
    return 2.0 * m["num_hidden_layers"] * per


def window_flops(m: dict, *, prompt_lens, contexts) -> float:
    """Model FLOPs of a window on this chip. prompt_lens: the length of
    each prompt admitted in it (row t scores its t + 1 index keys and
    attends min(t + 1, topk) of them); contexts: for each output token
    received in it, the keys its step scored. A prompt's last position
    yields the first output token: the head is counted once an output
    token."""
    L, k = m["num_hidden_layers"], sizes(m)["topk"]
    n = np.asarray(prompt_lens, np.float64)
    ctx = np.asarray(contexts, np.float64)
    tri = lambda a: a * (a + 1) / 2  # noqa: E731
    sel = np.where(n <= k, tri(n), tri(k) + (n - k) * k)
    prefill = (token_flops(m) * n.sum() + L * (
        index_flops_per_key(m) * tri(n).sum()
        + attn_flops_per_key(m) * sel.sum()))
    decode = ((token_flops(m) + 2.0 * head_params(m)) * ctx.size + L * (
        index_flops_per_key(m) * ctx.sum()
        + attn_flops_per_key(m) * attended(m, ctx).sum()))
    return float(prefill + decode)


def weight_bytes(m: dict) -> float:
    """What a decode step reads once on this chip: every layer's matmul
    weights, of the held experts those a step's pairs reach
    (`experts_touched` at the server's batch: every slot's row flows
    through a step, live or not), and the head."""
    rows = m["server"]["batch"]
    return float((m["num_hidden_layers"] * layer_params_read(m, rows)
                  + head_params(m)) * dtype_bytes(m))


def decode_token_bytes(m: dict, contexts) -> float:
    """Bytes the decode steps of these output tokens must move beside
    the weights, summed: every layer's index keys of the context and
    its K and V rows of the selected positions."""
    ctx = np.asarray(contexts, np.float64)
    return float(m["num_hidden_layers"] * (
        index_row_bytes(m) * ctx.sum()
        + kv_row_bytes(m) * attended(m, ctx).sum()))
