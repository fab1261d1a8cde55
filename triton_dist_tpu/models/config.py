"""Model configuration (reference: `python/triton_dist/models/config.py`
`ModelConfig:31` — hidden sizes, head counts, rope theta, loaded from HF
config.json when available)."""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Optional

import jax.numpy as jnp

from triton_dist_tpu.models.utils import EXPERTS_TOUCHED_COUNTERS


@dataclasses.dataclass(frozen=True)
class SAConfig:
    """Learned sparse attention (`sa_config` of a HF config: the
    DeepSeek sparse-attention indexer; layers/sparse_attn.py). One
    index key head a position; the published `q_chunk_size` /
    `kv_chunk_size` tile the score computation and change no result."""
    indexer_num_heads: int = 16
    indexer_head_dim: int = 64
    topk: int = 2048


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    hidden_size: int = 1024
    intermediate_size: int = 3072
    num_layers: int = 28
    num_heads: int = 16
    num_kv_heads: int = 8
    head_dim: int = 128
    vocab_size: int = 151936
    max_position_embeddings: int = 40960
    rope_theta: float = 1e6
    rms_norm_eps: float = 1e-6
    # default False to agree with the from_hf_config fallback; only the
    # <=4B Qwen3 models tie embeddings and they pass True explicitly
    tie_word_embeddings: bool = False
    model_type: str = "qwen3"
    # MoE (Qwen3-MoE family; 0 experts => dense)
    num_experts: int = 0
    num_experts_per_tok: int = 0
    moe_intermediate_size: int = 0
    dtype: str = "bfloat16"
    # learned sparse attention over the cached positions; None = every
    # query attends its whole context (models/qwen_moe.py)
    sa_config: Optional[SAConfig] = None
    # multi-section rotary: how many of the head's frequency pairs read
    # each component of a (time, height, width) position; () = one
    # position a token
    mrope_section: tuple = ()
    # a STATED SHARE of an expert-parallel deployment: (first, count) of
    # the `num_experts` the router ranks are held here (layers/ep_moe.py
    # `held`); None = all of them
    held_experts: Optional[tuple] = None

    @property
    def jax_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def expert_ids(self) -> range:
        """The published numbers of the experts whose load this server
        reports (`expert_tokens{expert=}`): the held ones."""
        first, count = self.held_experts or (0, self.num_experts)
        return range(first, first + count)

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def load_counters(self) -> tuple:
        """(name, help, labels) of the counters the scheduler folds the
        entries of a tick's routing-load vector into that follow
        [.., dropped, pairs routed, pairs held], in the order of the
        model's `_zero_load` (models/qwen_moe.py `_sa_ffn`)."""
        if self.sa_config is None:
            return ()
        return (
            ("sa_positions_in_context",
             "cached positions the decode steps' indexers scored, over "
             "slots and layers", None),
            ("sa_positions_attended",
             "those of sa_positions_in_context the steps attended: the "
             "positions the walk's mask let through, a slot and layer",
             None)) + EXPERTS_TOUCHED_COUNTERS

    @staticmethod
    def from_hf_config(path_or_dict) -> "ModelConfig":
        """Build from a HF config.json (reference: config.py loads HF
        configs by model name)."""
        if isinstance(path_or_dict, dict):
            d = path_or_dict
        else:
            p = path_or_dict
            if os.path.isdir(p):
                p = os.path.join(p, "config.json")
            with open(p) as f:
                d = json.load(f)
        return ModelConfig(
            hidden_size=d["hidden_size"],
            intermediate_size=d.get("intermediate_size", 0),
            num_layers=d["num_hidden_layers"],
            num_heads=d["num_attention_heads"],
            num_kv_heads=d.get("num_key_value_heads", d["num_attention_heads"]),
            head_dim=d.get("head_dim",
                           d["hidden_size"] // d["num_attention_heads"]),
            vocab_size=d["vocab_size"],
            max_position_embeddings=d.get("max_position_embeddings", 40960),
            rope_theta=d.get("rope_theta", 1e6),
            rms_norm_eps=d.get("rms_norm_eps", 1e-6),
            tie_word_embeddings=d.get("tie_word_embeddings", False),
            model_type=d.get("model_type", "qwen3"),
            num_experts=d.get("num_experts", 0),
            num_experts_per_tok=d.get("num_experts_per_tok", 0),
            moe_intermediate_size=d.get("moe_intermediate_size", 0),
        )


def tiny_qwen3(n: int = 8, **overrides) -> ModelConfig:
    """A tiny Qwen3-shaped config divisible by an n-way TP mesh — the
    test-model role of the reference's small test shapes."""
    base = dict(hidden_size=64, intermediate_size=128, num_layers=2,
                num_heads=2 * n, num_kv_heads=n, head_dim=32,
                vocab_size=256, max_position_embeddings=128,
                dtype="float32")
    base.update(overrides)
    return ModelConfig(**base)


def tiny_qwen3_moe(n: int = 8, **overrides) -> ModelConfig:
    """A tiny Qwen3-MoE-shaped config divisible by an n-way mesh."""
    base = dict(hidden_size=64, intermediate_size=0, num_layers=2,
                num_heads=2 * n, num_kv_heads=n, head_dim=32,
                vocab_size=256, max_position_embeddings=128,
                num_experts=2 * n, num_experts_per_tok=2,
                moe_intermediate_size=32, dtype="float32")
    base.update(overrides)
    return ModelConfig(**base)


def qwen3_30b_a3b() -> ModelConfig:
    """Qwen3-30B-A3B shapes (the MoE family's flagship; reference:
    models/qwen_moe.py targets Qwen3-MoE checkpoints)."""
    return ModelConfig(hidden_size=2048, intermediate_size=6144,
                       num_layers=48, num_heads=32, num_kv_heads=4,
                       head_dim=128, vocab_size=151936,
                       num_experts=128, num_experts_per_tok=8,
                       moe_intermediate_size=768)


def qwen3_1p7b() -> ModelConfig:
    """Qwen3-1.7B shapes — the single-chip bench model (fits a v5e)."""
    return ModelConfig(hidden_size=2048, intermediate_size=6144,
                       num_layers=28, num_heads=16, num_kv_heads=8,
                       head_dim=128, vocab_size=151936,
                       tie_word_embeddings=True)


def qwen3_32b() -> ModelConfig:
    """Qwen3-32B shapes (the reference megakernel/e2e target,
    docs/getting-started/megakernel/megakernel.md:29)."""
    return ModelConfig(hidden_size=5120, intermediate_size=25600,
                       num_layers=64, num_heads=64, num_kv_heads=8,
                       head_dim=128, vocab_size=151936)
