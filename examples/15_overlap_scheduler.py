"""Overlap scheduler: the host loop hides behind device compute
(the SGLang zero-overhead overlap design, 2312.07104 — PAPERS.md) —
plus the int8 paged pool that halves decode KV bandwidth.

A synchronous serving poll blocks on the previous tick's readback
before any host bookkeeping runs (admissions, drafting, the radix-tree
inserts, socket writes) — so at large slot counts the HOST becomes the
inter-token floor even though the device finished long ago. With
``ContinuousScheduler(overlap=True)`` the driver dispatches tick N+1
BEFORE reading back tick N: the same host work now runs while the
device computes, every blocking readback is one coalesced
``jax.device_get``, and token streams stay BITWISE identical.
``TokenServer`` dispatches ahead by default; ``overlap=False`` there,
and the scheduler's own default, is the synchronous control this demo
compares against, not a tuning choice.

This demo serves the same request mix three ways and prints:
- overlap off/on: identical streams, and the ``host_ms_per_poll``
  gauge (dispatch-to-dispatch host time minus device wait — the work
  the pipeline hides);
- the int8 PAGED pool (``kv_dtype=jnp.int8``): per-page scale planes
  ride the page payload through sharing/CoW/eviction, the paged flash
  kernel dequants in-kernel, and streams match the contiguous-int8
  reference bitwise while the pool holds ~2x the pages per byte.

Run on CPU (no TPU needed):
  JAX_PLATFORMS=cpu \
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python examples/15_overlap_scheduler.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import _common  # noqa: E402
_common.bootstrap()              # widen the CPU substrate BEFORE jax loads

import numpy as np  # noqa: E402


def main():
    import jax.numpy as jnp
    from triton_dist_tpu.models import (AutoLLM, ContinuousScheduler,
                                        Engine, Request)
    from triton_dist_tpu.models.config import tiny_qwen3
    from triton_dist_tpu.runtime import initialize_distributed

    ctx = initialize_distributed()
    cfg = tiny_qwen3(ctx.tp_size())
    model = AutoLLM.from_config(cfg, ctx.mesh)

    rng = np.random.RandomState(0)
    prefix = rng.randint(0, cfg.vocab_size, size=(12,)).astype(np.int32)

    def requests():
        out = []
        r2 = np.random.RandomState(1)
        for i in range(5):
            tail = r2.randint(0, cfg.vocab_size,
                              size=(4 + 3 * (i % 3),)).astype(np.int32)
            ids = np.concatenate([prefix, tail]) if i % 2 else tail
            out.append(Request(rid=i, ids=ids.astype(np.int32),
                               gen_len=10 + 2 * (i % 2), seed=7 + i))
        return out

    # --- overlap off vs on over the paged pool with prefix sharing
    eng = Engine(model, max_seq=64, backend="xla")
    runs = {}
    for overlap in (False, True):
        sched = ContinuousScheduler(eng, batch=3, chunk=4, paged=True,
                                    page=8, prefill_budget=4,
                                    overlap=overlap)
        runs[overlap] = (sched.run(requests()), sched.stats())

    for rid, toks in runs[False][0].items():
        assert np.array_equal(runs[True][0][rid], toks), \
            f"rid={rid}: overlap changed the stream"
    print("overlap-on streams bitwise identical to overlap-off: yes")
    for overlap in (False, True):
        st = runs[overlap][1]
        print(f"  overlap={str(overlap):5s} host_ms_per_poll="
              f"{st['host_ms_per_poll']:.2f} "
              f"device_wait_s={st['device_wait_s']:.3f}")
    print("  (host_ms_per_poll is the work the dispatch-ahead loop "
          "hides under device compute; on real chips the sync loop's "
          "inter-token floor is exactly this number)")

    # --- int8 paged pool vs the contiguous int8 reference
    eng8 = Engine(model, max_seq=64, backend="xla", kv_dtype=jnp.int8)
    contig = ContinuousScheduler(eng8, batch=3, chunk=4).run(requests())
    paged8 = ContinuousScheduler(eng8, batch=3, chunk=4, paged=True,
                                 page=8, overlap=True)
    got = paged8.run(requests())
    for rid, toks in contig.items():
        assert np.array_equal(got[rid], toks), \
            f"rid={rid}: int8 paged diverged from contiguous int8"
    st = paged8.stats()
    print("int8 paged pool (overlap on) bitwise identical to the "
          "contiguous int8 cache: yes")
    print(f"  prefix hits={st['hits']} — scale planes follow pages "
          f"through the radix tree for free")
    print("OK")


if __name__ == "__main__":
    main()
