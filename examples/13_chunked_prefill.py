"""Chunked prefill: a LONG prompt is admitted mid-decode without
stalling the live streams (Sarathi-Serve, 2403.02310 — PAPERS.md).

Two clients are streaming tokens when a third arrives with a prompt an
order of magnitude longer. Monolithically, its admission runs the whole
prompt as ONE prefill program and every live stream's next token waits
behind it — the inter-token latency spike Sarathi-Serve measures.
With `prefill_budget` set, the scheduler absorbs the prompt in budgeted
chunks FUSED into the regular decode step (one mixed forward per poll,
riding the same per-slot q_lens/kv_lens kernel masks speculative
verify uses), so the live streams emit a token on every poll while the
long prompt soaks in — and every stream is BITWISE identical to the
monolithic run.

Run on CPU (no TPU needed):
  JAX_PLATFORMS=cpu \
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python examples/13_chunked_prefill.py
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

    ctx = initialize_distributed()
    cfg = tiny_qwen3(ctx.tp_size())
    model = AutoLLM.from_config(cfg, ctx.mesh)
    eng = Engine(model, max_seq=96, backend="xla")

    rng = np.random.RandomState(0)
    live = [Request(rid=f"live{i}",
                    ids=rng.randint(0, cfg.vocab_size,
                                    size=(4,)).astype(np.int32),
                    gen_len=32)
            for i in range(2)]
    long_req = Request(
        rid="long",
        ids=rng.randint(0, cfg.vocab_size, size=(48,)).astype(np.int32),
        gen_len=4)
    budget = 6

    def serve(prefill_budget):
        sched = ContinuousScheduler(eng, batch=3, chunk=1,
                                    prefill_budget=prefill_budget)
        for r in live:
            sched.submit(r)
        acc = {r.rid: [] for r in live + [long_req]}
        live_emitted_during_absorb = 0
        absorb_polls = 0
        for _ in range(3):                # live slots armed + streaming
            out, _ = sched.poll()
            for rid, t in out.items():
                acc[rid].extend(t.tolist())
        sched.submit(long_req)
        while not acc["long"] and not sched.idle:
            out, _ = sched.poll()
            absorb_polls += 1
            live_emitted_during_absorb += sum(
                len(t) for rid, t in out.items() if rid != "long")
            for rid, t in out.items():
                acc[rid].extend(t.tolist())
        while not sched.idle:
            out, _ = sched.poll()
            for rid, t in out.items():
                acc[rid].extend(t.tolist())
        return acc, sched.stats(), absorb_polls, \
            live_emitted_during_absorb

    acc_c, st_c, polls_c, live_c = serve(budget)
    acc_m, st_m, _, _ = serve(None)

    print(f"long prompt: {len(long_req.ids)} tokens, "
          f"prefill_budget={budget}")
    print(f"  monolithic: max prefill tokens in one poll = "
          f"{st_m['max_prefill_tokens_per_poll']} (the whole prompt "
          f"stalls every live stream)")
    print(f"  chunked:    max prefill tokens in one poll = "
          f"{st_c['max_prefill_tokens_per_poll']} "
          f"(<= budget {budget})")
    print(f"  chunked absorption took {polls_c} polls; live streams "
          f"emitted {live_c} tokens during it "
          f"({live_c / max(polls_c, 1):.1f}/poll — no stall)")

    assert st_c["max_prefill_tokens_per_poll"] <= budget
    assert st_m["max_prefill_tokens_per_poll"] == len(long_req.ids)
    assert polls_c >= 2 and live_c >= 2 * (polls_c - 1)
    for rid in acc_m:
        assert acc_c[rid] == acc_m[rid], (
            f"{rid}: chunked and monolithic streams diverged")
    print("chunked streams bitwise identical to monolithic: yes")
    print("OK")


if __name__ == "__main__":
    main()
