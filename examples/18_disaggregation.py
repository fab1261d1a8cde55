"""Prefill/decode disaggregation: dedicated prefill workers stream KV
pages to decode workers over the transfer plane (the DistServe split,
2401.09670 — PAPERS.md; models/disagg.py has the design).

Chunked prefill BOUNDS the stall a long admission puts on live decode
streams; disaggregation REMOVES it: admissions prefill on a dedicated
worker (its own staging paged pool — on a real deployment its own mesh
slice), the finished page-groups cross the transfer plane in the
host-tier wire format (raw page bytes, one-DMA gather/scatter), and
the decode mesh installs them through the radix tree and arms the
slot. Decode ticks never carry a prefill q_len again —
``stats()["max_prefill_tokens_per_poll"]`` is structurally 0.

This demo admits a LONG prompt into a busy decode batch three ways and
prints:
- fused monolithic / fused chunked / disaggregated streams BITWISE
  identical (same tokens, same PRNG chains);
- the decode-mesh prefill counters: fused forwards every prompt token
  on the decode mesh, disagg forwards ZERO (they land in
  ``prefill_plane_tokens`` instead);
- the transfer-plane telemetry: kv_transfers, pages_transferred,
  transfer_bytes, kv_transfer_latency_ms.

Run on CPU (no TPU needed):
  JAX_PLATFORMS=cpu \
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python examples/18_disaggregation.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import _common  # noqa: E402
_common.bootstrap()              # widen the CPU substrate BEFORE jax loads

import numpy as np  # noqa: E402


def main():
    from triton_dist_tpu.models import (AutoLLM, ContinuousScheduler,
                                        DisaggScheduler, Engine, Request)
    from triton_dist_tpu.models.config import tiny_qwen3
    from triton_dist_tpu.runtime import initialize_distributed

    ctx = initialize_distributed()
    cfg = tiny_qwen3(ctx.tp_size())
    model = AutoLLM.from_config(cfg, ctx.mesh)
    eng = Engine(model, max_seq=96, backend="xla")

    def requests():
        rng = np.random.RandomState(0)
        out = [Request(rid=i,
                       ids=rng.randint(0, cfg.vocab_size,
                                       size=(5 + 2 * i,)).astype(np.int32),
                       gen_len=12, seed=20 + i)
               for i in range(3)]
        # the long admission: 48 prompt tokens into the busy batch
        out.append(Request(
            rid="long",
            ids=rng.randint(0, cfg.vocab_size,
                            size=(48,)).astype(np.int32),
            gen_len=8, seed=99))
        return out

    fused = ContinuousScheduler(eng, batch=4, chunk=2,
                                paged=True).run(requests())
    chunked_sched = ContinuousScheduler(eng, batch=4, chunk=2,
                                        paged=True, prefill_budget=8)
    chunked = chunked_sched.run(requests())
    disagg_sched = DisaggScheduler(eng, batch=4, chunk=2)
    disagg = disagg_sched.run(requests())
    disagg_sched.close()

    for rid in fused:
        assert np.array_equal(chunked[rid], fused[rid]), rid
        assert np.array_equal(disagg[rid], fused[rid]), rid
    print("disagg == fused-chunked == fused-monolithic streams "
          "(bitwise): yes")

    st_c, st_d = chunked_sched.stats(), disagg_sched.stats()
    print(f"  fused chunked : decode-mesh prefill tokens="
          f"{st_c['prefill_tokens_forwarded']:.0f} "
          f"max/poll={st_c['max_prefill_tokens_per_poll']}")
    print(f"  disaggregated : decode-mesh prefill tokens="
          f"{st_d['prefill_tokens_forwarded']:.0f} "
          f"max/poll={st_d['max_prefill_tokens_per_poll']} "
          f"(plane forwarded {st_d['prefill_plane_tokens']})")
    assert st_d["max_prefill_tokens_per_poll"] == 0
    lat = st_d["kv_transfer_latency_ms"]
    print(f"  transfer plane: kv_transfers={st_d['kv_transfers']} "
          f"pages={st_d['pages_transferred']} "
          f"bytes={st_d['transfer_bytes']} "
          f"latency p50={lat['p50']:.2f}ms p99={lat['p99']:.2f}ms")
    print("  (on real chips the prefill plane is its own mesh slice "
          "and the payload rides the ICI/DCN transports — "
          "kernels/p2p.py p2p_push_pages, two_tier.py kv_push_slices)")
    print("OK")


if __name__ == "__main__":
    main()
