"""Models + inference engine (reference: python/triton_dist/models/,
SURVEY.md §2.4). `AutoLLM` dispatches by model name/config the way the
reference does (models/__init__.py:33-59: Qwen3 -> DenseLLM,
Qwen3-MoE -> Qwen3MoE)."""

from triton_dist_tpu.models.afmoe import (Afmoe, AfmoeConfig,  # noqa: F401
                                          tiny_afmoe)
from triton_dist_tpu.models.config import (ModelConfig, SAConfig,  # noqa: F401
                                           qwen3_30b_a3b, qwen3_32b,
                                           tiny_qwen3, tiny_qwen3_moe)
from triton_dist_tpu.models.deepseek import (DeepSeekConfig,  # noqa: F401
                                             DeepSeekV3, tiny_deepseek)
from triton_dist_tpu.models.dense import DenseLLM  # noqa: F401
from triton_dist_tpu.models.disagg import (DCNTransport,  # noqa: F401
                                           DisaggScheduler,
                                           HostTransport, ICITransport,
                                           KVHandoff, PrefillWorker,
                                           PrefillWorkerDied)
from triton_dist_tpu.models.engine import Engine  # noqa: F401
from triton_dist_tpu.models.kv_cache import (HybridSlotCache,  # noqa: F401
                                             IndexedSlotCache, KVCache,
                                             LatentSlotCache,
                                             PagedSlotCache)
from triton_dist_tpu.models.phi4flash import (Phi4Flash,  # noqa: F401
                                              Phi4FlashConfig,
                                              tiny_phi4flash)
from triton_dist_tpu.models.prefix_cache import (PoolExhausted,  # noqa: F401
                                                 PrefixCache)
from triton_dist_tpu.models.scheduler import (ContinuousScheduler,  # noqa: F401
                                              DecodeSlots,
                                              PagedDecodeSlots, Request,
                                              ResumeState)
from triton_dist_tpu.models.spec_decode import (Drafter,  # noqa: F401
                                                NgramDrafter)


class AutoLLM:
    """Name-based dispatch (reference: AutoLLM.from_pretrained,
    models/__init__.py:33-59)."""

    @staticmethod
    def from_pretrained(path: str, mesh, axis: str = "tp", **kw):
        cfg = ModelConfig.from_hf_config(path)
        if cfg.is_moe:
            from triton_dist_tpu.models.qwen_moe import Qwen3MoE
            return Qwen3MoE.from_hf(path, mesh, axis, **kw)
        _dense_kw_check(kw)
        return DenseLLM.from_hf(path, mesh, axis, **kw)

    @staticmethod
    def from_config(cfg: ModelConfig, mesh, axis: str = "tp", seed: int = 0,
                    **kw):
        if cfg.is_moe:
            from triton_dist_tpu.models.qwen_moe import Qwen3MoE
            return Qwen3MoE.random_init(cfg, mesh, axis, seed, **kw)
        _dense_kw_check(kw)
        return DenseLLM.random_init(cfg, mesh, axis, seed, **kw)


def _dense_kw_check(kw) -> None:
    """Dense models take the sequence-parallel kwargs only (the sp
    serving layout — models/dense.py); everything else is MoE-only."""
    extra = set(kw) - {"sp_axis", "sp_combine"}
    assert not extra, f"MoE-only kwargs {sorted(extra)} on a dense config"
