"""TP-sharded paged serving: one scheduler drives a TP=N mesh.

The whole serving stack built over the paged pool — continuous
batching, radix prefix cache, chunked prefill, spec decode, overlap —
runs TP-NATIVE (ROADMAP open item 1): the pool's page payloads carry
a head-group axis sharded over the mesh (models/kv_cache.py
PagedSlotCache TP SHARDING), the slot attends run under jax.shard_map
with each chip walking only its own kv-head shard
(layers/tp_attn.py), and the projections route through the TP
backends — so a TP=N mesh serves at N× the aggregate FLOPs and KV
bandwidth per token while the allocator, radix tree, CoW and
preemption logic stay host-side and layout-oblivious.

This demo runs the SAME multi-tenant burst (shared system prompt,
mixed lengths) through a single-chip engine and a TP=4 engine and
shows:
- token streams BITWISE identical across topologies,
- the prefix-cache hit counters agreeing (policy is layout-blind),
- stats() reporting tp_size + aggregate AND per-chip tok/s.

Run on CPU (no TPU needed):
  JAX_PLATFORMS=cpu \
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python examples/17_tp_serving.py
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

    TP = min(4, len(jax.devices()))
    cfg = tiny_qwen3(TP)

    # one config, two topologies: random_init is mesh-independent, so
    # the weights are bitwise identical — only the layout differs
    rng = np.random.RandomState(0)
    system = rng.randint(0, cfg.vocab_size, size=(8,)).astype(np.int32)
    reqs = []
    for i, (tail, gen) in enumerate([(4, 6), (7, 8), (3, 5), (9, 6)]):
        ids = np.concatenate(
            [system,
             rng.randint(0, cfg.vocab_size, size=(tail,))]
        ).astype(np.int32)
        reqs.append(Request(rid=i, ids=ids, gen_len=gen, seed=50 + i))

    def serve(n):
        mesh = jax.make_mesh((n,), ("tp",))
        model = AutoLLM.from_config(cfg, mesh)
        eng = Engine(model, max_seq=64, backend="flash")
        sched = ContinuousScheduler(eng, batch=3, chunk=2, paged=True,
                                    page=8)
        out = sched.run([dataclasses.replace(r) for r in reqs])
        return out, sched.stats()

    out1, st1 = serve(1)
    outN, stN = serve(TP)

    for r in reqs:
        np.testing.assert_array_equal(
            outN[r.rid], out1[r.rid],
            err_msg=f"rid={r.rid} diverged across topologies")
    assert stN["hits"] == st1["hits"] and stN["hits"] > 0

    print(f"served {len(reqs)} requests on TP=1 and TP={TP}: "
          f"streams bitwise identical")
    print(f"  prefix-cache hits (both topologies): {stN['hits']}, "
          f"prefill tokens skipped: {stN['prefill_tokens_skipped']}")
    for label, st in (("TP=1 ", st1), (f"TP={TP}", stN)):
        print(f"  {label}: tp_size={st['tp_size']} "
              f"aggregate={st['serving_tok_per_s_aggregate']} tok/s "
              f"per-chip={st['serving_tok_per_s_per_chip']} tok/s")
    print("(on this CPU smoke all 'chips' share the host's cores — "
          "real TPU meshes are where the aggregate scales)")
    print("OK")


if __name__ == "__main__":
    main()
