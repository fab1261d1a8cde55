"""Layers (reference analog: python/triton_dist/layers/nvidia/,
SURVEY.md §2.4): TP building blocks over the overlapped kernel library,
with the reference's forward-mode switch (xla oracle / overlapped dist /
AR / fused GEMM-AR)."""

from triton_dist_tpu.layers.common import (  # noqa: F401
    rms_norm,
    precompute_rope,
    apply_rope,
    shard_cols_packed,
)
from triton_dist_tpu.layers.tp_mlp import TP_MLP  # noqa: F401
from triton_dist_tpu.layers.tp_attn import TP_Attn  # noqa: F401
from triton_dist_tpu.layers.tp_moe import TP_MoE  # noqa: F401
from triton_dist_tpu.layers.ep_moe import EP_MoE  # noqa: F401
from triton_dist_tpu.layers.mla_attn import MLA_Attn  # noqa: F401
from triton_dist_tpu.layers.sparse_attn import SA_Attn  # noqa: F401
from triton_dist_tpu.layers.gated_attn import GatedAttn  # noqa: F401
from triton_dist_tpu.layers.sp_attn import (  # noqa: F401
    SPAttn,
    UlyssesAttn,
)
from triton_dist_tpu.layers.pp import PPipeline, train_1f1b  # noqa: F401
