"""MoE paged serving: Qwen3-MoE behind the full serving stack.

The serving stack — continuous batching, radix prefix cache, paged KV,
chunked prefill, spec decode, overlap — is MODEL-BLIND (ISSUE 13):
`Qwen3MoE` carries the same slot surface `DenseLLM` does
(`forward_tokens_slots_paged` + the verify/mixed twins), with per-slot
top-k routing run INSIDE every decode tick and the expert MLPs
dispatched through the grouped-GEMM kernel (kernels/group_gemm.py) —
the megablox-style pattern of vLLM-TPU (SNIPPETS.md [1]) — or through
the EP a2a wire when the experts are sharded (moe_impl="ep",
backend="ep_flash").

This demo:
- serves a multi-tenant burst (shared system prompt) through
  ContinuousScheduler(paged=True) over a TP-MoE Qwen3MoE,
- shows the streams BITWISE equal to sequential Engine.serve() calls,
- prints the per-expert load gauges (`expert_tokens{expert=...}`), the
  `moe_capacity_drops` counter and the `expert_load_imbalance` gauge —
  the observable half of the dropless-or-loud capacity contract,
- when >= 2 devices are visible, serves a second burst through an
  expert-SHARDED model (EP, same config) over the a2a dispatch and
  shows its streams bitwise equal that engine's own serve().

Run on CPU (no TPU needed):
  JAX_PLATFORMS=cpu \
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python examples/19_moe_serving.py
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
    from triton_dist_tpu.models.config import tiny_qwen3_moe

    cfg = tiny_qwen3_moe(1, num_experts=4)       # E=4 experts, top-2
    rng = np.random.RandomState(0)
    system = rng.randint(0, cfg.vocab_size, size=(8,)).astype(np.int32)
    reqs = []
    for i, (tail, gen) in enumerate([(4, 6), (7, 8), (3, 5), (9, 6)]):
        ids = np.concatenate(
            [system, rng.randint(0, cfg.vocab_size, size=(tail,))]
        ).astype(np.int32)
        reqs.append(Request(rid=i, ids=ids, gen_len=gen, seed=50 + i))

    # --- TP-MoE serving: experts replicated, grouped-GEMM dispatch
    mesh1 = jax.make_mesh((1,), ("tp",))
    model = AutoLLM.from_config(cfg, mesh1, capacity_factor="dropless")
    eng = Engine(model, max_seq=64, backend="flash")
    sched = ContinuousScheduler(eng, batch=3, chunk=2, paged=True,
                                page=8)
    out = sched.run([dataclasses.replace(r) for r in reqs])

    for r in reqs:
        want = np.asarray(eng.serve(np.tile(r.ids[None], (3, 1)),
                                    r.gen_len))[0]
        np.testing.assert_array_equal(out[r.rid], want)
    st = sched.stats()
    print(f"served {len(reqs)} requests through the paged MoE "
          f"scheduler: streams bitwise equal sequential serve()")
    print(f"  prefix-cache hits: {st['hits']} "
          f"(prefill tokens skipped: {st['prefill_tokens_skipped']})")
    loads = {e: st.get(f"expert_tokens{{expert={e}}}", 0)
             for e in range(cfg.num_experts)}
    print(f"  expert load (routed entries): {loads}")
    print(f"  capacity drops: {st['moe_capacity_drops']} "
          f"(dropless config), load imbalance max/mean: "
          f"{st['expert_load_imbalance']:.2f}")

    # --- EP serving: the SAME config expert-sharded over the a2a wire
    # (some jax builds' interpret mode cannot run the one-sided a2a
    # kernels — the known dma_start discharge limitation; the demo
    # then reports and moves on, exactly like the skip-guarded tests)
    if len(jax.devices()) >= 2:
        try:
            mesh2 = jax.make_mesh((2,), ("tp",))
            model_ep = AutoLLM.from_config(
                tiny_qwen3_moe(2, num_experts=4), mesh2, moe_impl="ep",
                capacity_factor="dropless")
            eng_ep = Engine(model_ep, max_seq=64, backend="ep_flash")
            sched_ep = ContinuousScheduler(eng_ep, batch=2, chunk=2,
                                           paged=True, page=8)
            cfg2 = model_ep.config
            rng2 = np.random.RandomState(1)
            reqs_ep = [Request(rid=i,
                               ids=rng2.randint(0, cfg2.vocab_size,
                                                size=(6 + i,)
                                                ).astype(np.int32),
                               gen_len=5) for i in range(3)]
            out_ep = sched_ep.run(
                [dataclasses.replace(r) for r in reqs_ep])
            for r in reqs_ep:
                want = np.asarray(eng_ep.serve(
                    np.tile(r.ids[None], (2, 1)), r.gen_len))[0]
                np.testing.assert_array_equal(out_ep[r.rid], want)
            print(f"EP serving (experts sharded over 2 chips, tokens "
                  f"over the a2a wire): {len(reqs_ep)} streams bitwise "
                  f"equal serve()")
        except AssertionError:
            raise        # a real stream divergence must fail the demo
        except Exception as e:
            print(f"EP arm skipped: interpret-mode a2a kernels "
                  f"unavailable here ({type(e).__name__})")

    print("OK")


if __name__ == "__main__":
    main()
