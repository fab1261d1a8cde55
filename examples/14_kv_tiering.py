"""Host-RAM KV tier: a prefix working set LARGER than the device pool
survives eviction in host memory and comes back bitwise.

Four tenants' system prompts rotate through a device page pool sized
for roughly ONE of them. Without the tier, every return visit finds
its prefix LRU-evicted and re-prefills from scratch. With
`host_pool_pages` set (triton_dist_tpu/models/kv_tier.py + the
residency state machine in models/prefix_cache.py), eviction DEMOTES
each prefix's page-groups to host RAM (one d2h gather across every
layer's pool) and the return visit PROMOTES them back into fresh
device pages (one h2d install) before prefilling only its own suffix —
the effective cache becomes device + host pages. The demo asserts the
token streams are bitwise identical tier-on vs tier-off vs cache-off,
while the printed counters show the spans actually travelling through
the host pool.

Run on CPU (no TPU needed):
  JAX_PLATFORMS=cpu \
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python examples/14_kv_tiering.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import _common  # noqa: E402
_common.bootstrap()              # widen the CPU substrate BEFORE jax loads

import numpy as np  # noqa: E402


def main():
    from triton_dist_tpu.models import (AutoLLM, ContinuousScheduler,
                                        Engine, Request)
    from triton_dist_tpu.models.config import tiny_qwen3
    from triton_dist_tpu.runtime import initialize_distributed
    from triton_dist_tpu.serving import ByteTokenizer

    ctx = initialize_distributed()
    cfg = tiny_qwen3(ctx.tp_size())
    model = AutoLLM.from_config(cfg, ctx.mesh)
    eng = Engine(model, max_seq=64, backend="xla")
    tok = ByteTokenizer(cfg.vocab_size)

    page, chunk, gen = 8, 4, 6
    tenants = ["Avery's terse TPU sage. ", "Blake, a verbose bard!! ",
               "Casey the careful clerk ", "Devon =) daring daemon. "]
    questions = ["ping?", "again", "more!?"]
    # two visits per tenant, interleaved so every return visit finds
    # its prefix displaced from the device pool by the other tenants
    reqs = [Request(rid=i, ids=np.asarray(
                tok.encode(tenants[i % 4] + questions[i % 3]),
                np.int32), gen_len=gen)
            for i in range(8)]
    pre_tokens = len(tok.encode(tenants[0]))

    # device pool: ~one worst-case slot; host pool: the whole set
    Hkv = cfg.num_kv_heads
    worst = -(-(pre_tokens + 8 + gen + chunk - 1) // page)
    num_pages = worst * Hkv + 1 + Hkv
    host_pages = 4 * worst * Hkv * 2

    runs, stats = {}, {}
    for label, kw in (
            ("cache-off", dict(prefix_cache=False, num_pages=num_pages)),
            ("tier-off", dict(num_pages=num_pages)),
            ("tier-on", dict(num_pages=num_pages,
                             host_pool_pages=host_pages))):
        sched = ContinuousScheduler(eng, batch=1, chunk=chunk,
                                    paged=True, page=page, **kw)
        runs[label] = sched.run(reqs)
        stats[label] = sched.stats()

    on, off = stats["tier-on"], stats["tier-off"]
    print(f"4 tenants x 2 visits, {pre_tokens}-token prefixes, device "
          f"pool {num_pages} pages (~1 slot), host pool {host_pages} "
          f"pages:")
    print(f"  tier-off: hit_rate {off['hit_rate']:.2f}, prefill "
          f"skipped {off['prefill_tokens_skipped']} tokens "
          f"(returning prefixes were evicted)")
    print(f"  tier-on:  hit_rate {on['hit_rate']:.2f}, prefill "
          f"skipped {on['prefill_tokens_skipped']} tokens")
    print(f"            demotions {on['demotions']}, promotions "
          f"{on['promotions']}, host_hits {on['host_hits']}, "
          f"host_pages_resident {on['host_pages_resident']}/"
          f"{on['host_pool_pages']}, restore EMA "
          f"{on['restore_latency_ms']:.2f} ms")

    assert on["demotions"] > 0 and on["promotions"] > 0
    assert on["host_hits"] >= 2
    assert on["prefill_tokens_skipped"] > off["prefill_tokens_skipped"]
    for r in reqs:
        a = runs["tier-on"][r.rid]
        assert np.array_equal(a, runs["tier-off"][r.rid]), r.rid
        assert np.array_equal(a, runs["cache-off"][r.rid]), r.rid
    print("warm-from-host streams bitwise identical to recompute: yes")
    print("OK")


if __name__ == "__main__":
    main()
