"""Long-context serving: sequence-parallel paged decode under the
scheduler (ISSUE 14 — the serving promotion of the repo's SP kernel
suite; Ring Attention arXiv:2310.01889 sets the blockwise cross-chip
attention pattern, Infinite-LLM/DistAttention arXiv:2401.02669 the
cluster-wide paged-KV deployment story).

With `sp_axis` set on the model, the paged pool's PAGE-ID space shards
over the sp mesh axis (models/kv_cache.py PagedSlotCache SP SHARDING):
chip s holds physical pages [s*NP/S, (s+1)*NP/S) of every layer, the
host allocator rotates fresh page groups across shards, and each
decode tick walks only its local pages through the split-KV partial
kernel (kernels/paged_kv.flash_decode_paged_partial) before the
cross-chip LSE combine (kernels/sp_flash_decode.sp_combine_partials)
merges the partial softmaxes — per-chip KV reads and attention FLOPs
drop to ~1/S, and a slot's max context is bounded by the WHOLE mesh's
paged HBM instead of one chip's.

This demo shows the capability jump, not a speedup (on the CPU
substrate all "chips" timeshare the host):
- a long request whose KV footprint exceeds one chip's pool is
  HARD-REJECTED upfront by an sp=1 scheduler,
- the same request ADMITS and decodes under sp=4 with the same
  per-chip pool size,
- where both fit, the sp=4 stream is BITWISE equal to a single-chip
  scheduler's,
- stats() reports sp_size, per-shard page residency and the
  sp_combine device-wait attribution.

Run on CPU (no TPU needed):
  JAX_PLATFORMS=cpu \
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python examples/20_long_context.py
"""

import dataclasses
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import _common  # noqa: E402
_common.bootstrap()              # widen the CPU substrate BEFORE jax loads


def main():
    import jax
    import numpy as np

    from triton_dist_tpu.models import (AutoLLM, ContinuousScheduler,
                                        Engine, Request)
    from triton_dist_tpu.models.config import tiny_qwen3

    SP = min(4, len(jax.devices()))
    cfg = tiny_qwen3(4)
    page = 8
    chip_pages = 4 + 1                  # one chip's pool: 4 pages + trash

    # one config, two topologies — random_init is mesh-independent, so
    # the weights are bitwise identical; only the pool layout differs
    model_1 = AutoLLM.from_config(cfg, jax.make_mesh((1,), ("tp",)))
    model_sp = AutoLLM.from_config(
        cfg, jax.make_mesh((1, SP), ("tp", "sp")), sp_axis="sp")
    eng_1 = Engine(model_1, max_seq=128, backend="flash")
    eng_sp = Engine(model_sp, max_seq=128, backend="flash")

    long_doc = Request(
        rid="doc",
        ids=(np.arange(40) % cfg.vocab_size).astype(np.int32),
        gen_len=10, seed=7)

    # --- sp=1, one chip's pool: the admission hard-rejects UPFRONT ---
    s1 = ContinuousScheduler(eng_1, batch=1, paged=True, chunk=2,
                             page=page, num_pages=chip_pages)
    out = s1.run([dataclasses.replace(long_doc)])
    print(f"sp=1 ({chip_pages} pages/chip): "
          f"rejected -> {s1.rejected['doc'][:64]}...")
    assert "doc" in s1.rejected and not out.get("doc", ()).__len__()

    # --- sp=4, the SAME per-chip pool x4 chips: admits and decodes ---
    s4 = ContinuousScheduler(eng_sp, batch=1, paged=True, chunk=2,
                             page=page, num_pages=chip_pages * SP)
    out4 = s4.run([dataclasses.replace(long_doc)])
    st = s4.stats()
    print(f"sp={SP} (same pool/chip): {len(out4['doc'])} tokens; "
          f"sp_size={st['sp_size']}, "
          f"resident by shard={st['sp_pages_resident']}, "
          f"sp_combine wait={st['device_wait_s_by_kind']['sp_combine']}s")

    # --- bitwise vs a big single-chip pool (where both fit) ---
    sb = ContinuousScheduler(eng_1, batch=1, paged=True, chunk=2,
                             page=page)
    outB = sb.run([dataclasses.replace(long_doc)])
    assert np.array_equal(out4["doc"], outB["doc"])
    print("stream bitwise equal to the single-chip reference — "
          f"max context grew x{SP} for free")


if __name__ == "__main__":
    main()
