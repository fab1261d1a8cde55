#!/usr/bin/env python
"""Round benchmark: Qwen3-1.7B greedy decode throughput on the available
chip(s), normalized against the reference's published per-chip decode
throughput (BASELINE.md: Qwen3-32B TP8 decode bsz=128 ctx=128 GEMM-AR
mode, 12.41 ms/step on 8x H800 => 1289 tok/s/chip at 4B params/chip,
docs/getting-started/e2e/e2e_dense.md:38).

vs_baseline is FLOPs-normalized across model sizes:
    (our tok/s/chip * our params/chip) / (1289 * 4e9)

Decode at this batch is HBM-bandwidth-bound, so the single-chip run
uses the framework's bandwidth configuration: int8 weight storage
(kernels/quant.py — dequant after each dot, exact per-column scaling)
and an int8 KV cache (per-position scales folded into the flash
kernel's logits/P — kernels/flash_attn.py). Timing loop, model, batch
and context are unchanged from previous rounds.

One process, no fallback: the rows come from whatever backend JAX
initialises, each row names it ("backend"), and a failure — no
backend, a kernel that does not compile, the roofline report — ends
the run non-zero. Rows: {"metric", "value", "unit", "vs_baseline",
"backend"}, one JSON object per line.
"""

import json
import os
import subprocess
import sys
import time

_METRIC = "qwen3_decode_tok_per_s_per_chip"
_SERVE_METRIC = "serving_tok_per_s_per_chip"

# perf-regression ledger (tools/bench_compare.py): every capture
# appends to BENCH_history.jsonl next to this script — one JSON line
# per row, stamped with a per-invocation run id, git sha, host and
# timestamp so runs can be grouped and same-window pairs compared
# (this class of host swings >25% between boxes — the comparer, not
# the ledger, owns the noise policy). TDTPU_BENCH_HISTORY overrides
# the path; set it EMPTY to disable.
_HISTORY_DEFAULT = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "BENCH_history.jsonl")
_RUN_ID = f"{int(time.time())}-{os.getpid()}"
_GIT_SHA = None


def _git_sha():
    global _GIT_SHA
    if _GIT_SHA is None:
        try:
            _GIT_SHA = subprocess.run(
                ["git", "rev-parse", "--short", "HEAD"],
                cwd=os.path.dirname(os.path.abspath(__file__)),
                capture_output=True, text=True, timeout=10,
            ).stdout.strip() or "unknown"
        except (OSError, subprocess.SubprocessError):
            _GIT_SHA = "unknown"
    return _GIT_SHA


def _history_append(obj):
    """Best-effort ledger append; a read-only checkout or full disk
    must never fail the bench."""
    path = os.environ.get("TDTPU_BENCH_HISTORY", _HISTORY_DEFAULT)
    if not path:
        return
    import platform
    row = dict(obj, run=_RUN_ID, git_sha=_git_sha(),
               host=platform.node(), unix=round(time.time(), 3))
    try:
        with open(path, "a") as f:
            f.write(json.dumps(row) + "\n")
    except OSError:
        pass


def _emit_json(obj):
    """One bench row: stdout (the driver's capture) + optional file
    capture when TDTPU_BENCH_JSON names a path (append, one JSON line
    per row — ad-hoc runs keep their history without tee plumbing) +
    the BENCH_history.jsonl perf-regression ledger (every capture,
    diffable over time with tools/bench_compare.py)."""
    line = json.dumps(obj)
    print(line, flush=True)
    path = os.environ.get("TDTPU_BENCH_JSON")
    if path:
        try:
            with open(path, "a") as f:
                f.write(line + "\n")
        except OSError:
            pass
    _history_append(obj)


def _bench():
    import jax
    import jax.numpy as jnp
    import numpy as np

    on_tpu = jax.default_backend() == "tpu"
    from triton_dist_tpu.models import AutoLLM, Engine
    from triton_dist_tpu.models.config import qwen3_1p7b, tiny_qwen3
    # the central kernel enumeration (ISSUE 15): stamp captures with
    # the registry size so a bench row's kernel surface is dated —
    # tdcheck, kprof and perf_report read the same table
    from triton_dist_tpu.kernels import kernel_registry

    ndev = len(jax.devices())
    mesh = jax.make_mesh((ndev,), ("tp",))
    rows_extra = {"kernels_registered": len(kernel_registry())}

    if on_tpu:
        cfg = qwen3_1p7b()
        B, S, gen = 128, 128, 128
        params = 1.7e9
    else:
        # CPU smoke configuration so the bench always produces a line
        cfg = tiny_qwen3(ndev)
        B, S, gen = 2, 8, 4
        params = 1e6

    model = AutoLLM.from_config(cfg, mesh)
    # single chip runs the framework's Pallas flash-decode + fused SwiGLU
    # kernels; multi-chip runs the fused GEMM+AR comm kernels. BOTH run
    # the int8 bandwidth configuration on real hardware: the comm
    # kernels stream int8 weight panels and dequant per column after
    # the dot (kernels/quant.py contract inside
    # ag_gemm/gemm_rs/gemm_allreduce), so the decode-bandwidth win
    # survives multi-chip TP.
    # TDTPU_BENCH_BACKEND overrides the choice — e.g. "xla" to capture
    # the scheduler-level rows on a host whose Pallas interpret mode
    # cannot run the comm kernels (the rows are then about the serving
    # loop, not the kernels; the default stays the measured config)
    backend = os.environ.get("TDTPU_BENCH_BACKEND") or (
        "flash" if ndev == 1 else "gemm_ar")
    kv_dtype = None
    if on_tpu:
        model = model.quantize_int8()
        kv_dtype = jnp.int8
    eng = Engine(model, max_seq=S + gen + 8, backend=backend,
                 kv_dtype=kv_dtype)

    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, size=(B, S)).astype(np.int32)

    # The reference's baseline number is a DECODE step time (12.41 ms/step,
    # e2e_dense.md:38), so time the decode scan only — prefill is warmed
    # and timed apart. np.asarray forces a host readback, so the device
    # work is finished inside the timed region.
    logits, cache = eng.prefill(ids)
    _ = np.asarray(logits.sum())
    toks = eng.decode(logits, cache, gen)
    _ = np.asarray(toks)  # warmup (compile)

    iters = 3 if on_tpu else 1
    dts = []
    for _ in range(iters):
        logits, cache = eng.prefill(ids)
        _ = np.asarray(logits.sum())
        t0 = time.perf_counter()
        toks = eng.decode(logits, cache, gen)
        _ = np.asarray(toks)
        dts.append(time.perf_counter() - t0)
    dt = min(dts)

    tok_s = B * gen / dt
    tok_s_chip = tok_s / ndev
    # reference: 1289 tok/s/chip at 4e9 params/chip (BASELINE.md)
    params_per_chip = params / ndev
    vs_baseline = (tok_s_chip * params_per_chip) / (1289.0 * 4e9)

    _emit_json({
        "metric": _METRIC,
        "value": round(tok_s_chip, 2),
        "unit": "tok/s/chip",
        "vs_baseline": round(vs_baseline, 4),
        "backend": jax.default_backend(),
        **rows_extra,
    })

    # --- continuous-batching serving row: N DISTINCT prompts of mixed
    # gen_lens through the slot scheduler (models/scheduler.py) — the
    # multi-client serving rate, where the old single-request loop did
    # duplicate work in B-1 of B rows. Aggregate tokens / wall time,
    # admission + refill included (that IS serving).
    from triton_dist_tpu.models.scheduler import ContinuousScheduler, Request
    if on_tpu:
        n_req, base_gen, s_len, chunk = 2 * B, 96, 96, 16
    else:
        n_req, base_gen, s_len, chunk = 4, 6, 6, 2
    rng = np.random.RandomState(1)
    reqs = [Request(rid=i,
                    ids=rng.randint(0, cfg.vocab_size,
                                    size=(s_len,)).astype(np.int32),
                    gen_len=base_gen + (i % 4) * max(base_gen // 8, 1))
            for i in range(n_req)]
    serve_batch = B if on_tpu else 2
    sched = ContinuousScheduler(eng, batch=serve_batch, chunk=chunk)
    sched.run(reqs[:1])                      # warm the slot programs
    sched = ContinuousScheduler(eng, batch=serve_batch, chunk=chunk)
    t0 = time.perf_counter()
    out = sched.run(reqs)
    dt = time.perf_counter() - t0
    total = sum(len(t) for t in out.values())
    s_tok_chip = total / dt / ndev
    _emit_json({
        "metric": _SERVE_METRIC,
        "value": round(s_tok_chip, 2),
        "unit": "tok/s/chip",
        "vs_baseline": round((s_tok_chip * params_per_chip)
                             / (1289.0 * 4e9), 4),
        "backend": jax.default_backend(),
        "requests": n_req, "slots": serve_batch,
    })

    # --- shared-prefix cache row: N requests sharing a system prompt
    # through the paged radix-cache scheduler (models/prefix_cache.py).
    # Reports the fraction of prompt prefill skipped plus the cold vs
    # warm shared-prefix TTFT (admission + first chunk) — the latency
    # win a returning tenant sees once its system prompt is cached.
    if on_tpu:
        pre_len, tail, p_gen, p_chunk, p_batch, n_share = 96, 16, 32, 8, 8, 8
    else:
        pre_len, tail, p_gen, p_chunk, p_batch, n_share = 24, 4, 4, 2, 2, 3
    # fresh engine: the paged pool stores the raw dtype (no int8 KV)
    eng_p = Engine(model, max_seq=pre_len + tail + p_gen + p_chunk + 16,
                   backend=backend)
    rng = np.random.RandomState(2)
    prefix = rng.randint(0, cfg.vocab_size, size=(pre_len,))
    p_reqs = [Request(rid=i,
                      ids=np.concatenate(
                          [prefix, rng.randint(0, cfg.vocab_size,
                                               size=(tail,))]
                      ).astype(np.int32),
                      gen_len=p_gen)
              for i in range(n_share)]

    def ttft(sched, req):
        sched.submit(req)
        t0 = time.perf_counter()
        while True:
            out, done = sched.poll()
            if req.rid in out or req.rid in done:
                return time.perf_counter() - t0

    def drain(sched):
        while not sched.idle:
            sched.poll()

    # compile warmup on a throwaway scheduler: one COLD admission (full
    # prompt bucket) and one WARM admission (suffix bucket) so the
    # measured TTFTs time admissions, not XLA compiles
    sched = ContinuousScheduler(eng_p, batch=p_batch, chunk=p_chunk,
                                paged=True, prefix_cache=True, page=16)
    ttft(sched, Request(rid="w0", ids=p_reqs[0].ids, gen_len=p_gen))
    drain(sched)
    ttft(sched, Request(
        rid="w1",
        ids=np.concatenate(
            [prefix, rng.randint(0, cfg.vocab_size, size=(tail,))]
        ).astype(np.int32),
        gen_len=p_gen))
    drain(sched)
    sched = ContinuousScheduler(eng_p, batch=p_batch, chunk=p_chunk,
                                paged=True, prefix_cache=True, page=16)
    ttft_cold = ttft(sched, p_reqs[0])     # empty tree: full prefill
    drain(sched)
    ttft_warm = ttft(sched, p_reqs[1])     # prefix cached: suffix only
    for r in p_reqs[2:]:
        sched.submit(r)
    drain(sched)
    st = sched.stats()
    _emit_json({
        "metric": "prefix_hit_prefill_skip_frac",
        "value": round(st["prefill_skip_frac"], 4),
        "unit": "frac",
        "prefix_tokens": pre_len,
        "requests": n_share,
        "hit_rate": round(st["hit_rate"], 4),
        "ttft_cold_ms": round(ttft_cold * 1e3, 2),
        "ttft_warm_ms": round(ttft_warm * 1e3, 2),
        "backend": jax.default_backend(),
    })

    # --- speculative decoding row (models/spec_decode.py): n-gram
    # self-drafted multi-token verify on a REPETITIVE workload (the
    # summarization/self-quoting regime prompt-lookup targets — here a
    # periodic prompt that pulls greedy decode into a loop the drafter
    # locks onto). Reports accepted tokens per verify forward (> 1.0 is
    # the win: decode is weight-bandwidth-bound, so tokens-per-forward
    # is the latency lever) and the accept rate, with the spec-off
    # scheduler timed on the same requests as the baseline.
    if on_tpu:
        sp_gen, sp_batch, sp_K, period, reps = 96, 16, 4, 4, 16
    else:
        sp_gen, sp_batch, sp_K, period, reps = 48, 2, 4, 4, 6
    rng = np.random.RandomState(3)
    pat = np.tile(rng.randint(0, cfg.vocab_size, size=(period,)), reps)

    def spec_reqs():
        return [Request(rid=i,
                        ids=np.concatenate(
                            [pat, pat[:2]]).astype(np.int32),
                        gen_len=sp_gen)
                for i in range(sp_batch)]

    eng_s = Engine(model, max_seq=len(pat) + 2 + sp_gen + 8,
                   backend=backend, kv_dtype=kv_dtype)
    times = {}
    stats_on = None
    for K in (0, sp_K):
        sched = ContinuousScheduler(eng_s, batch=sp_batch, chunk=4,
                                    spec=K)
        sched.run(spec_reqs())            # warm the programs
        sched = ContinuousScheduler(eng_s, batch=sp_batch, chunk=4,
                                    spec=K)
        t0 = time.perf_counter()
        out = sched.run(spec_reqs())
        times[K] = time.perf_counter() - t0
        if K:
            stats_on = sched.stats()
        assert all(len(t) == sp_gen for t in out.values())
    _emit_json({
        "metric": "spec_decode_tokens_per_step",
        "value": round(stats_on["tokens_per_step"], 4),
        "unit": "tok/forward",
        "accept_rate": round(stats_on["spec_accept_rate"], 4),
        "spec": sp_K,
        "baseline_tokens_per_step": 1.0,
        "tok_per_s_spec": round(sp_batch * sp_gen / times[sp_K], 2),
        "tok_per_s_base": round(sp_batch * sp_gen / times[0], 2),
        "backend": jax.default_backend(),
    })

    # --- preemption/resume overhead row (models/scheduler.py
    # resilience): the SAME mixed workload through an AMPLE pool vs a
    # pool sized to force KV-pressure preemption (fits roughly half the
    # slots' worst case). Reports the throughput ratio — the price of
    # degrading gracefully instead of rejecting — plus the preemption
    # count; streams are asserted identical (the exactness contract,
    # tests/test_resilience.py).
    if on_tpu:
        pr_len, pr_gen, pr_batch, pr_n, pr_page = 64, 48, 8, 16, 16
    else:
        pr_len, pr_gen, pr_batch, pr_n, pr_page = 10, 8, 2, 4, 8
    pr_chunk = 4
    Hkv = cfg.num_kv_heads

    def pr_reqs():
        r2 = np.random.RandomState(5)
        return [Request(rid=i,
                        ids=r2.randint(0, cfg.vocab_size,
                                       size=(pr_len,)).astype(np.int32),
                        gen_len=pr_gen)
                for i in range(pr_n)]

    worst = -(-(pr_len + pr_gen + pr_chunk - 1) // pr_page)
    tiny = max(1, pr_batch // 2) * worst * Hkv + 1 + Hkv
    eng_r = Engine(model, max_seq=pr_len + pr_gen + pr_chunk + 16,
                   backend=backend)
    pr_times, pr_outs, pr_preempts = {}, {}, 0
    for label, npages in (("ample", None), ("tiny", tiny)):
        sched = ContinuousScheduler(eng_r, batch=pr_batch,
                                    chunk=pr_chunk, paged=True,
                                    prefix_cache=True, page=pr_page,
                                    num_pages=npages)
        sched.run(pr_reqs()[:1])          # warm the programs
        sched = ContinuousScheduler(eng_r, batch=pr_batch,
                                    chunk=pr_chunk, paged=True,
                                    prefix_cache=True, page=pr_page,
                                    num_pages=npages)
        t0 = time.perf_counter()
        pr_outs[label] = sched.run(pr_reqs())
        pr_times[label] = time.perf_counter() - t0
        if label == "tiny":
            pr_preempts = sched.preemptions
    assert all(np.array_equal(pr_outs["tiny"][i], pr_outs["ample"][i])
               for i in range(pr_n)), "preempted streams diverged"
    total = pr_n * pr_gen
    _emit_json({
        "metric": "preempt_resume_overhead",
        "value": round(pr_times["tiny"] / pr_times["ample"], 4),
        "unit": "x slowdown",
        "preemptions": pr_preempts,
        "tok_per_s_tiny_pool": round(total / pr_times["tiny"], 2),
        "tok_per_s_ample_pool": round(total / pr_times["ample"], 2),
        "tiny_pool_pages": tiny,
        "requests": pr_n, "slots": pr_batch,
        "backend": jax.default_backend(),
    })

    # --- chunked-prefill rows (models/scheduler.py step_mixed,
    # Sarathi-Serve 2403.02310): a LONG prompt admitted into a busy
    # decode batch. ttft_under_decode_load_ms is the long request's
    # submit-to-first-token under that load, chunked (prefill_budget)
    # vs monolithic; inter_token_p99_ms is the p99 (and max) wall-clock
    # gap between consecutive tokens of the LIVE streams while the
    # prompt is absorbed — the head-of-line stall the chunk budget
    # bounds (monolithically the whole prompt prefills inside one poll
    # and every live stream's next token waits behind it).
    if on_tpu:
        cl_live, cl_plen, cl_gen, cl_long, cl_budget = 6, 16, 192, 384, 32
    else:
        cl_live, cl_plen, cl_gen, cl_long, cl_budget = 2, 4, 24, 32, 4
    eng_c = Engine(model, max_seq=cl_long + cl_gen + 16, backend=backend,
                   kv_dtype=kv_dtype)

    def chunked_load_run(budget):
        rngc = np.random.RandomState(6)
        live = [Request(rid=f"l{i}",
                        ids=rngc.randint(0, cfg.vocab_size,
                                         size=(cl_plen,)).astype(np.int32),
                        gen_len=cl_gen)
                for i in range(cl_live)]
        long_req = Request(
            rid="long",
            ids=rngc.randint(0, cfg.vocab_size,
                             size=(cl_long,)).astype(np.int32),
            gen_len=8)
        sched = ContinuousScheduler(eng_c, batch=cl_live + 1, chunk=2,
                                    prefill_budget=budget)
        for r in live:
            sched.submit(r)
        for _ in range(4):                 # live slots armed + decoding
            sched.poll()
        last = {r.rid: time.perf_counter() for r in live}
        gaps = []
        t_submit = time.perf_counter()
        sched.submit(long_req)
        ttft = None
        while ttft is None:
            out, done = sched.poll()
            now = time.perf_counter()
            for r in live:
                if len(out.get(r.rid, ())):
                    gaps.append(now - last[r.rid])
                    last[r.rid] = now
            if len(out.get("long", ())):
                ttft = now - t_submit
            elif "long" in done:
                break                      # rejected — keep the gaps
        while not sched.idle:
            sched.poll()
        return ttft, gaps

    res = {}
    for label, budget in (("chunked", cl_budget), ("monolithic", None)):
        chunked_load_run(budget)           # warm the programs
        res[label] = chunked_load_run(budget)
    p99 = {k: float(np.percentile(v[1], 99) * 1e3) for k, v in res.items()}
    gmax = {k: float(np.max(v[1]) * 1e3) for k, v in res.items()}
    _emit_json({
        "metric": "ttft_under_decode_load_ms",
        "value": round(res["chunked"][0] * 1e3, 2),
        "unit": "ms",
        "monolithic_ms": round(res["monolithic"][0] * 1e3, 2),
        "prompt_tokens": cl_long, "prefill_budget": cl_budget,
        "live_streams": cl_live,
        "backend": jax.default_backend(),
    })
    _emit_json({
        "metric": "inter_token_p99_ms",
        "value": round(p99["chunked"], 2),
        "unit": "ms",
        "monolithic_p99_ms": round(p99["monolithic"], 2),
        "max_gap_chunked_ms": round(gmax["chunked"], 2),
        "max_gap_monolithic_ms": round(gmax["monolithic"], 2),
        "prompt_tokens": cl_long, "prefill_budget": cl_budget,
        "live_streams": cl_live,
        "backend": jax.default_backend(),
    })

    # --- disaggregation rows (models/disagg.py — the DistServe split,
    # 2401.09670): a long prompt admitted into a busy decode batch
    # with prefill traffic FULLY OFF the decode mesh — a dedicated
    # prefill worker thread computes the prompt's KV into a staging
    # pool and streams the pages to the decode pool, so decode polls
    # never carry a prefill q_len. Both arms are measured by the SAME
    # harness over the live streams' WHOLE serving window (not just
    # the absorption tail — the sustained p99 a client actually sees):
    # the fused chunked arm's mixed ticks pay up to `prefill_budget`
    # prompt tokens on the decode forward's critical path for every
    # tick of the absorption, while the disagg arm pays one install
    # (visible as max_gap — on real chips the h2d overlaps decode; on
    # this same-host smoke the worker also timeshares the CPU, which
    # separate prefill chips do not). disagg_ttft_ms is the long
    # request's TTFT (prefill + transfer + install, overlapped with
    # the live decode). Best-of-two per arm against CPU noise.
    from triton_dist_tpu.models.disagg import DisaggScheduler

    if on_tpu:
        dl_live, dl_plen, dl_gen, dl_long, dl_budget = 6, 16, 256, 384, 32
    else:
        dl_live, dl_plen, dl_gen, dl_long, dl_budget = 3, 4, 40, 48, 4

    def disagg_load_run(disagg):
        rngc = np.random.RandomState(6)
        live = [Request(rid=f"l{i}",
                        ids=rngc.randint(0, cfg.vocab_size,
                                         size=(dl_plen,)).astype(np.int32),
                        gen_len=dl_gen)
                for i in range(dl_live)]
        long_req = Request(
            rid="long",
            ids=rngc.randint(0, cfg.vocab_size,
                             size=(dl_long,)).astype(np.int32),
            gen_len=8)
        if disagg:
            sched = DisaggScheduler(eng_c, batch=dl_live + 1, chunk=2,
                                    threads=True)
        else:
            sched = ContinuousScheduler(eng_c, batch=dl_live + 1,
                                        chunk=2, paged=True,
                                        prefill_budget=dl_budget)
        try:
            for r in live:
                sched.submit(r)
            for _ in range(200):           # live slots armed + decoding
                sched.poll()
                if len(sched.slots.occupied) >= dl_live:
                    break
            last = {r.rid: time.perf_counter() for r in live}
            gaps = []
            t_submit = time.perf_counter()
            sched.submit(long_req)
            ttft = None
            while not sched.idle:          # the WHOLE serving window
                out, done = sched.poll()
                now = time.perf_counter()
                for r in live:
                    if len(out.get(r.rid, ())):
                        gaps.append(now - last[r.rid])
                        last[r.rid] = now
                if ttft is None and len(out.get("long", ())):
                    ttft = now - t_submit
        finally:
            if disagg:
                sched.close()
        return ttft, gaps

    dres = {}
    for arm in (False, True):
        disagg_load_run(arm)               # warm the programs
        a, b = disagg_load_run(arm), disagg_load_run(arm)
        pick = a if np.percentile(a[1], 99) <= np.percentile(b[1], 99) \
            else b
        dres[arm] = pick
    d_p99 = {k: float(np.percentile(v[1], 99) * 1e3)
             for k, v in dres.items()}
    d_max = {k: float(np.max(v[1]) * 1e3) for k, v in dres.items()}
    _emit_json({
        "metric": "disagg_inter_token_p99_ms",
        "value": round(d_p99[True], 2),
        "unit": "ms",
        "fused_chunked_p99_ms": round(d_p99[False], 2),
        "max_gap_disagg_ms": round(d_max[True], 2),
        "max_gap_fused_chunked_ms": round(d_max[False], 2),
        "gap_samples": len(dres[True][1]),
        "prompt_tokens": dl_long, "prefill_budget": dl_budget,
        "live_streams": dl_live, "prefill_workers": 1,
        "transport": "host",
        "backend": jax.default_backend(),
    })
    _emit_json({
        "metric": "disagg_ttft_ms",
        "value": round(dres[True][0] * 1e3, 2),
        "unit": "ms",
        "fused_chunked_ttft_ms": round(dres[False][0] * 1e3, 2),
        "prompt_tokens": dl_long, "prefill_budget": dl_budget,
        "live_streams": dl_live, "prefill_workers": 1,
        "transport": "host",
        "backend": jax.default_backend(),
    })

    # --- overlap scheduler rows (models/scheduler.py overlap=True —
    # the SGLang zero-overhead overlap design, PAPERS.md): the SAME
    # mixed serving workload through the synchronous poll loop and the
    # dispatch-ahead pipeline. Re-captures serving_tok_per_s_per_chip
    # and inter_token_p99_ms overlap-on (each row carries its
    # overlap-off twin), plus the NEW host_ms_per_poll row — the
    # dispatch-to-dispatch host time with device wait subtracted, i.e.
    # the work the pipeline hides under device compute. On CPU the
    # "device" is the host too, so the tok/s delta is noise; the gauge
    # pair is the signal, and real chips are where the p99 gap opens.
    if on_tpu:
        ov_n, ov_len, ov_gen, ov_batch, ov_chunk = 2 * B, 64, 96, B, 8
    else:
        ov_n, ov_len, ov_gen, ov_batch, ov_chunk = 6, 8, 10, 3, 2
    eng_o = Engine(model, max_seq=ov_len + ov_gen + ov_chunk + 16,
                   backend=backend)

    def ov_reqs():
        r = np.random.RandomState(8)
        return [Request(rid=i,
                        ids=r.randint(0, cfg.vocab_size,
                                      size=(ov_len,)).astype(np.int32),
                        gen_len=ov_gen, seed=i)
                for i in range(ov_n)]

    def ov_run(overlap, trace=False):
        mk = lambda: ContinuousScheduler(eng_o, batch=ov_batch,
                                         chunk=ov_chunk, paged=True,
                                         overlap=overlap, trace=trace)
        mk().run(ov_reqs()[:1])            # warm the programs
        sched = mk()
        for r in ov_reqs():
            sched.submit(r)
        last, gaps, total = {}, [], 0
        t0 = time.perf_counter()
        while not sched.idle:
            out, _ = sched.poll()
            now = time.perf_counter()
            for rid, t in out.items():
                if len(t):
                    if rid in last:
                        gaps.append(now - last[rid])
                    last[rid] = now
                    total += len(t)
        dt = time.perf_counter() - t0
        return total / dt, gaps, sched.stats()

    ov = {flag: ov_run(flag) for flag in (False, True)}
    _emit_json({
        "metric": _SERVE_METRIC,
        "value": round(ov[True][0] / ndev, 2),
        "unit": "tok/s/chip",
        "overlap": True,
        "overlap_off_tok_per_s_per_chip": round(ov[False][0] / ndev, 2),
        "requests": ov_n, "slots": ov_batch,
        "backend": jax.default_backend(),
    })
    _emit_json({
        "metric": "inter_token_p99_ms",
        "value": round(float(np.percentile(ov[True][1], 99) * 1e3), 2),
        "unit": "ms",
        "overlap": True,
        "overlap_off_p99_ms": round(
            float(np.percentile(ov[False][1], 99) * 1e3), 2),
        "requests": ov_n, "slots": ov_batch,
        "backend": jax.default_backend(),
    })
    _emit_json({
        "metric": "host_ms_per_poll",
        "value": ov[True][2]["host_ms_per_poll"],
        "unit": "ms",
        "overlap": True,
        "overlap_off_ms": ov[False][2]["host_ms_per_poll"],
        "device_wait_s_on": ov[True][2]["device_wait_s"],
        "device_wait_s_off": ov[False][2]["device_wait_s"],
        "requests": ov_n, "slots": ov_batch,
        "backend": jax.default_backend(),
    })

    # --- telemetry overhead row (runtime/telemetry.py): the SAME
    # overlap workload with full tracing ON (registry + request event
    # rings + poll-timeline spans + device-occupancy stamps) vs the
    # trace-off run above. Tracing is host-side only and the hot-path
    # records are O(1)/zero-alloc, so this should be noise — the row
    # is the regression tripwire that keeps it that way. The traced
    # run's LIVE latency histograms ride along (ttft/inter-token p99
    # measured by the registry itself, vs this bench's own stopwatch).
    # best-of-two per arm: on the CPU smoke single runs vary by >10%
    # from scheduler-thread interference alone, which would swamp the
    # signal (real chips pin the device side and shrink the noise)
    tr1 = ov_run(True, trace=True)
    tokps_traced = max(tr1[0], ov_run(True, trace=True)[0])
    st_traced = tr1[2]
    tokps_off = max(ov[True][0], ov_run(True)[0])
    overhead = (tokps_off - tokps_traced) / tokps_off * 100.0
    _emit_json({
        "metric": "telemetry_overhead_pct",
        "value": round(overhead, 2),
        "unit": "%",
        "tok_per_s_traced": round(tokps_traced / ndev, 2),
        "tok_per_s_off": round(tokps_off / ndev, 2),
        "live_ttft_p99_ms": st_traced["ttft_ms"]["p99"],
        "live_inter_token_p99_ms": st_traced["inter_token_ms"]["p99"],
        "requests": ov_n, "slots": ov_batch,
        "backend": jax.default_backend(),
    })

    # --- host KV tier rows (models/kv_tier.py + the residency machine
    # in models/prefix_cache.py): kv_tier_warm_ttft_ms is a returning
    # tenant's TTFT when its prefix was DEMOTED to host RAM (h2d
    # promote + suffix prefill) vs a pure HBM hit vs full recompute —
    # the latency ladder the tier buys; kv_tier_capacity_multiplier is
    # the prefix hit rate on a working set LARGER than the device pool,
    # tier on vs off (off: returning prefixes were evicted and
    # recompute; on: they come back from host RAM), alongside the raw
    # capacity ratio (device + host) / device.
    if on_tpu:
        kt_pre, kt_tail, kt_gen, kt_n, kt_page = 96, 16, 32, 6, 16
    else:
        kt_pre, kt_tail, kt_gen, kt_n, kt_page = 24, 4, 4, 4, 8
    kt_chunk = 4
    eng_t = Engine(model, max_seq=kt_pre + kt_tail + kt_gen + kt_chunk
                   + 16, backend=backend)
    rng = np.random.RandomState(7)
    kt_pres = [rng.randint(0, cfg.vocab_size, size=(kt_pre,))
               for _ in range(kt_n)]

    def kt_req(rid, p, seed_tail):
        r2 = np.random.RandomState(seed_tail)
        return Request(rid=rid, ids=np.concatenate(
            [kt_pres[p], r2.randint(0, cfg.vocab_size,
                                    size=(kt_tail,))]).astype(np.int32),
            gen_len=kt_gen)

    worst = -(-(kt_pre + kt_tail + kt_gen + kt_chunk - 1) // kt_page)
    kt_pages = worst * Hkv + 1 + Hkv          # fits ~one slot's prefixes
    kt_host = kt_n * worst * Hkv * 2

    def kt_sched(host_pages, **kw):
        return ContinuousScheduler(
            eng_t, batch=1, chunk=kt_chunk, paged=True, page=kt_page,
            num_pages=kt_pages, host_pool_pages=host_pages, **kw)

    def kt_warm_run(sched):
        """Cold-admit prefix 0, displace it with prefix 1 (demotion),
        then time the return visit (promotion + suffix prefill)."""
        ttft(sched, kt_req("c0", 0, 10))
        drain(sched)
        ttft(sched, kt_req("c1", 1, 11))
        drain(sched)
        t = ttft(sched, kt_req("w", 0, 12))
        drain(sched)
        return t

    kt_warm_run(kt_sched(kt_host))            # warm every program
    sched = kt_sched(kt_host)
    ttft_host = kt_warm_run(sched)
    st_probe = sched.stats()
    assert st_probe["promotions"] >= 1, st_probe
    # HBM hit: same pool (same compiled programs), no displacement
    # between the cold admission and the return visit
    sched = kt_sched(0)
    ttft(sched, kt_req("c0", 0, 10))
    drain(sched)
    ttft_hbm = ttft(sched, kt_req("w", 0, 12))
    drain(sched)
    # recompute: cache off (same pool shape, same programs), full
    # prefill
    sched = kt_sched(0, prefix_cache=False)
    ttft_cold = ttft(sched, kt_req("w", 0, 12))
    drain(sched)
    _emit_json({
        "metric": "kv_tier_warm_ttft_ms",
        "value": round(ttft_host * 1e3, 2),
        "unit": "ms",
        "recompute_ms": round(ttft_cold * 1e3, 2),
        "hbm_hit_ms": round(ttft_hbm * 1e3, 2),
        "prefix_tokens": kt_pre,
        "restore_latency_ms": st_probe["restore_latency_ms"],
        "backend": jax.default_backend(),
    })

    # two passes over kt_n distinct prefixes through a ~1-slot pool:
    # pass 2 hits only via the host tier
    def kt_pass2(host_pages):
        sched = kt_sched(host_pages)
        for i in range(2 * kt_n):
            sched.submit(kt_req(i, i % kt_n, 20 + i))
        drain(sched)
        return sched.stats()

    kt_pass2(kt_host)                         # warm
    st_on = kt_pass2(kt_host)
    st_off = kt_pass2(0)
    _emit_json({
        "metric": "kv_tier_capacity_multiplier",
        "value": round((kt_pages + kt_host) / kt_pages, 2),
        "unit": "x pages",
        "hit_rate_tier": round(st_on["hit_rate"], 4),
        "hit_rate_no_tier": round(st_off["hit_rate"], 4),
        "skip_frac_tier": round(st_on["prefill_skip_frac"], 4),
        "skip_frac_no_tier": round(st_off["prefill_skip_frac"], 4),
        "host_hits": st_on["host_hits"],
        "demotions": st_on["demotions"],
        "promotions": st_on["promotions"],
        "device_pages": kt_pages, "host_pool_pages": kt_host,
        "working_set_prefixes": kt_n,
        "backend": jax.default_backend(),
    })

    # --- TP-sharded paged serving row (ROADMAP open item 1): the SAME
    # paged serving workload through ONE scheduler on the FULL TP mesh
    # (head-sharded pool, shard_map paged attends, comm-kernel
    # projections — models/kv_cache.py TP SHARDING) vs a single-chip
    # engine. Aggregate tokens/s across the mesh is the number TP
    # exists to scale; the per-chip twin rides in stats(). On the CPU
    # smoke every "chip" timeshares the same host cores, so the
    # on/off ratio is noise by construction — real chips
    # (tools/onchip_regen.sh) are the measurement.
    if on_tpu:
        tp_n, tp_len, tp_gen, tp_batch, tp_chunk = 2 * B, 64, 96, B, 8
    else:
        tp_n, tp_len, tp_gen, tp_batch, tp_chunk = 6, 8, 8, 3, 2

    def tp_reqs():
        r = np.random.RandomState(11)
        return [Request(rid=i,
                        ids=r.randint(0, cfg.vocab_size,
                                      size=(tp_len,)).astype(np.int32),
                        gen_len=tp_gen, seed=i)
                for i in range(tp_n)]

    def tp_run(eng_x):
        mk = lambda: ContinuousScheduler(eng_x, batch=tp_batch,
                                         chunk=tp_chunk, paged=True)
        mk().run(tp_reqs()[:1])            # warm the slot programs
        sched = mk()
        t0 = time.perf_counter()
        out = sched.run(tp_reqs())
        dt = time.perf_counter() - t0
        return sum(len(t) for t in out.values()) / dt, sched.stats()

    eng_tp = Engine(model, max_seq=tp_len + tp_gen + tp_chunk + 16,
                    backend=backend, kv_dtype=kv_dtype)
    agg_on, st_tp = tp_run(eng_tp)
    if ndev > 1:
        mesh_1 = jax.make_mesh((1,), ("tp",))
        model_1 = AutoLLM.from_config(cfg, mesh_1)
        if on_tpu:
            model_1 = model_1.quantize_int8()
        eng_1 = Engine(model_1,
                       max_seq=tp_len + tp_gen + tp_chunk + 16,
                       backend=os.environ.get("TDTPU_BENCH_BACKEND")
                       or "flash", kv_dtype=kv_dtype)
        agg_off, _ = tp_run(eng_1)
    else:
        agg_off = agg_on                   # single-chip host: on == off
    _emit_json({
        "metric": "serving_tok_per_s_aggregate",
        "value": round(agg_on, 2),
        "unit": "tok/s",
        "tp_size": ndev,
        "tp_off_tok_per_s": round(agg_off, 2),
        "per_chip": round(agg_on / ndev, 2),
        "stats_per_chip": st_tp.get("serving_tok_per_s_per_chip"),
        "requests": tp_n, "slots": tp_batch,
        "backend": jax.default_backend(),
    })

    # --- sequence-parallel long-context rows (ISSUE 14 / ROADMAP
    # long-context item): (a) the SAME fixed-context paged serving
    # burst with the pool's page-id space sharded over an sp axis
    # (split-KV partial walk + cross-chip LSE combine per tick) vs
    # sp-off — per-chip tok/s is the number sp trades for capacity;
    # (b) the capacity multiplier: the longest admissible context at a
    # FIXED per-chip pool, sp=S vs sp=1, probed through the exact
    # host-side admission gate (validate_admission — rejects are
    # host-only, so the probe is cheap and honest). On the CPU smoke
    # the throughput ratio is noise by construction (chips timeshare
    # the host; real chips via tools/onchip_regen.sh are the
    # measurement) but the capacity multiplier is exact everywhere.
    sp_n = min(4, ndev)
    if sp_n > 1:
        from triton_dist_tpu.models import Request as _Req
        mesh_sp = jax.make_mesh((1, sp_n), ("tp", "sp"))
        model_sp = AutoLLM.from_config(cfg, mesh_sp, sp_axis="sp")
        model_sp1 = AutoLLM.from_config(cfg, jax.make_mesh((1,), ("tp",)))
        sp_len, sp_gen2, sp_batch2 = (64, 96, 4) if on_tpu else (8, 8, 2)
        seq_cap = sp_len + sp_gen2 + 16

        def sp_reqs():
            r = np.random.RandomState(13)
            return [_Req(rid=i,
                         ids=r.randint(0, cfg.vocab_size,
                                       size=(sp_len,)).astype(np.int32),
                         gen_len=sp_gen2, seed=i)
                    for i in range(2 * sp_batch2)]

        def sp_run(eng_x, nchips):
            mk = lambda: ContinuousScheduler(eng_x, batch=sp_batch2,
                                             chunk=2, paged=True)
            mk().run(sp_reqs()[:1])        # warm the slot programs
            sched = mk()
            t0 = time.perf_counter()
            out = sched.run(sp_reqs())
            dt = time.perf_counter() - t0
            return sum(len(t) for t in out.values()) / dt / nchips

        eng_sp = Engine(model_sp, max_seq=seq_cap, backend="flash")
        eng_sp1 = Engine(model_sp1, max_seq=seq_cap, backend="flash")
        sp_on = sp_run(eng_sp, sp_n)
        sp_off = sp_run(eng_sp1, 1)

        # capacity probe: fixed per-chip pool, longest admissible
        # context through the real admission gate
        page_b = 16
        chip_pages = 8 * cfg.num_kv_heads + cfg.num_kv_heads

        def max_ctx(eng_x, pages):
            sched = ContinuousScheduler(eng_x, batch=1, paged=True,
                                        chunk=2, page=page_b,
                                        num_pages=pages)
            lo = 0
            for n in range(page_b, sched.slots.capacity, page_b):
                req = _Req(rid="probe",
                           ids=np.zeros((n,), np.int32), gen_len=1)
                try:
                    sched.slots.validate_admission(
                        req, np.zeros((n,), np.int32))
                    lo = n
                except ValueError:
                    break
            return lo

        cap_hint = page_b * (chip_pages * sp_n) // cfg.num_kv_heads
        eng_probe_sp = Engine(model_sp, max_seq=cap_hint,
                              backend="flash")
        eng_probe_1 = Engine(model_sp1, max_seq=cap_hint,
                             backend="flash")
        ctx_sp = max_ctx(eng_probe_sp, chip_pages * sp_n)
        ctx_1 = max_ctx(eng_probe_1, chip_pages)
        _emit_json({
            "metric": "sp_decode_tok_per_s_per_chip",
            "value": round(sp_on, 2),
            "unit": "tok/s",
            "sp_size": sp_n,
            "sp_off_tok_per_s_per_chip": round(sp_off, 2),
            "context_len": sp_len,
            "backend": jax.default_backend(),
        })
        _emit_json({
            "metric": "long_context_capacity_multiplier",
            "value": round(ctx_sp / max(ctx_1, 1), 2),
            "unit": "x",
            "sp_size": sp_n,
            "max_context_sp": ctx_sp,
            "max_context_sp1": ctx_1,
            "pages_per_chip": chip_pages,
            "backend": jax.default_backend(),
        })

    # --- AOT warm-start row (ISSUE 12: tools/aot.py AOTProgramCache):
    # wall seconds from Engine construction to a drained serving burst
    # on a COLD process-wide program cache, vs the same rebuild with
    # TDTPU_AOT_CACHE pointing at the blobs the cold run just wrote —
    # the restart cost an elastically added worker pays. xla-mode on
    # the CPU smoke (the exportable configuration there); real chips
    # export the kernel-bearing programs too.
    import shutil
    import tempfile
    from triton_dist_tpu.models import engine as _eng_mod
    aot_dir = tempfile.mkdtemp(prefix="tdtpu_aot_bench_")
    aot_backend = "flash" if on_tpu else "xla"
    # the temp cache dir is deleted below, so the claim AOTProgramCache
    # takes on jax's process-global compilation-cache config must be
    # released first (aot.release_compilation_cache); any user-set
    # TDTPU_AOT_CACHE is restored verbatim
    prev_aot_env = os.environ.get("TDTPU_AOT_CACHE")
    aot_caches = []
    try:
        os.environ["TDTPU_AOT_CACHE"] = aot_dir

        def aot_run():
            t0 = time.perf_counter()
            eng_a = Engine(model, max_seq=S + gen + 8,
                           backend=aot_backend, kv_dtype=kv_dtype)
            aot_caches.append(eng_a._aot)
            sched = ContinuousScheduler(eng_a, batch=2, chunk=2,
                                        paged=True, page=8)
            rnga = np.random.RandomState(12)
            sched.run([Request(rid=i,
                               ids=rnga.randint(
                                   0, cfg.vocab_size,
                                   size=(4,)).astype(np.int32),
                               gen_len=3) for i in range(2)])
            return time.perf_counter() - t0, eng_a._aot.stats()

        _eng_mod._jit_programs.cache_clear()
        cold_s, cold_stats = aot_run()
        _eng_mod._jit_programs.cache_clear()
        warm_s, warm_stats = aot_run()
        _emit_json({
            "metric": "aot_warm_start_s",
            "value": round(warm_s, 3),
            "unit": "s",
            "cold_start_s": round(cold_s, 3),
            "programs_loaded": warm_stats["loaded"],
            "programs_exported_cold": cold_stats["exported"],
            "programs_fallback_warm": warm_stats["fallback"],
            "aot_backend": aot_backend,
            "backend": jax.default_backend(),
        })
    finally:
        if prev_aot_env is None:
            os.environ.pop("TDTPU_AOT_CACHE", None)
        else:
            os.environ["TDTPU_AOT_CACHE"] = prev_aot_env
        for c in aot_caches:
            c.release_compilation_cache()
        shutil.rmtree(aot_dir, ignore_errors=True)

    # --- MoE serving rows (ISSUE 13 / ROADMAP item 1): Qwen3MoE
    # through the SAME paged serving stack — per-slot routing inside
    # the tick, grouped-GEMM expert dispatch — plus the layer-level
    # grouped-GEMM-vs-per-expert-dense-loop differential the dispatch
    # replaces. CPU smoke shapes off-chip; real chips via
    # tools/onchip_regen.sh per the ROADMAP standing note.
    from triton_dist_tpu.models.config import tiny_qwen3_moe
    mesh_m1 = jax.make_mesh((1,), ("tp",))
    if on_tpu:
        cfg_moe = tiny_qwen3_moe(
            1, hidden_size=1024, num_heads=8, num_kv_heads=4,
            head_dim=128, num_layers=4, num_experts=16,
            num_experts_per_tok=2, moe_intermediate_size=512,
            vocab_size=32768, dtype="bfloat16",
            max_position_embeddings=512)
        moe_n, moe_len, moe_gen, moe_batch = 16, 64, 64, 8
    else:
        cfg_moe = tiny_qwen3_moe(1, num_experts=4)
        moe_n, moe_len, moe_gen, moe_batch = 4, 8, 6, 2
    model_moe = AutoLLM.from_config(cfg_moe, mesh_m1,
                                    capacity_factor="dropless")
    eng_moe = Engine(model_moe, max_seq=moe_len + moe_gen + 16,
                     backend="flash")

    def moe_reqs():
        r = np.random.RandomState(13)
        return [Request(rid=i,
                        ids=r.randint(0, cfg_moe.vocab_size,
                                      size=(moe_len,)).astype(np.int32),
                        gen_len=moe_gen, seed=i)
                for i in range(moe_n)]

    def moe_run():
        sched = ContinuousScheduler(eng_moe, batch=moe_batch, chunk=4,
                                    paged=True, page=8)
        t0 = time.perf_counter()
        out = sched.run(moe_reqs())
        dt = time.perf_counter() - t0
        return sum(len(t) for t in out.values()) / dt, sched.stats()

    moe_run()                              # warm the slot programs
    moe_rate, st_moe = moe_run()
    _emit_json({
        "metric": "moe_serving_tok_per_s_per_chip",
        "value": round(moe_rate, 2),
        "unit": "tok/s",
        "model": "qwen3_moe",
        "num_experts": cfg_moe.num_experts,
        "top_k": cfg_moe.num_experts_per_tok,
        "capacity_drops": st_moe.get("moe_capacity_drops"),
        "expert_load_imbalance": st_moe.get("expert_load_imbalance"),
        "requests": moe_n, "slots": moe_batch,
        "backend": jax.default_backend(),
    })

    # layer-level dispatch differential: ONE decode tick's worth of
    # tokens through the routed grouped-GEMM path (fwd_local — what
    # the serving tick runs) vs the per-expert dense loop (fwd_xla —
    # every token through every expert). value = dense / grouped wall,
    # so > 1 means the grouped dispatch is winning; on the CPU smoke
    # the tiny shapes make it noise, real chips are the measurement.
    moe_layer = model_moe.layers[0].moe
    x_tick = jnp.asarray(
        np.random.RandomState(14).randn(
            max(moe_batch, 8), cfg_moe.hidden_size
        ).astype(np.float32)).astype(cfg_moe.jax_dtype)
    grouped_f = jax.jit(lambda m, x: m(x, "flash"))
    dense_f = jax.jit(lambda m, x: m(x, "xla"))

    def _moe_time(f, n=5):
        jax.block_until_ready(f(moe_layer, x_tick))   # compile + warm
        best = float("inf")
        for _ in range(n):
            t0 = time.perf_counter()
            jax.block_until_ready(f(moe_layer, x_tick))
            best = min(best, time.perf_counter() - t0)
        return best

    t_grouped = _moe_time(grouped_f)
    t_dense = _moe_time(dense_f)
    _emit_json({
        "metric": "moe_grouped_gemm_speedup",
        "value": round(t_dense / t_grouped, 3),
        "unit": "x",
        "grouped_us": round(t_grouped * 1e6, 1),
        "dense_loop_us": round(t_dense * 1e6, 1),
        "tick_tokens": int(x_tick.shape[0]),
        "num_experts": cfg_moe.num_experts,
        "backend": jax.default_backend(),
    })

    # --- structured generation rows (models/structured.py): (a) n=4
    # parallel sampling through the KV fork — ONE submit fans into n
    # decode slots sharing the prompt's pages (refcount+1, CoW
    # boundary), so n-1 of n prompt prefills are skipped; the row's
    # value is the measured prefill_skip_frac (≈ (n-1)/n) with the
    # 4-sequential-requests arm timed alongside and the fork streams
    # asserted bitwise equal to the sequential same-seed replays.
    # (b) grammar-constrained decode (JSON schema → token FSM masks)
    # with spec-K jump-ahead: deterministic grammar segments (fixed
    # keys, braces, literals) ride the verify window as forced drafts,
    # so constrained decoding is multi-token-per-forward — the row
    # compares jump-ahead on (spec=K) vs off (spec=0) vs the
    # unconstrained baseline on the same prompts.
    from triton_dist_tpu.models.structured import GrammarSpec, byte_vocab
    if on_tpu:
        fs_len, fs_gen, fs_n, fs_page = 96, 32, 4, 16
        cg_n, cg_gen, cg_K = 8, 64, 4
    else:
        fs_len, fs_gen, fs_n, fs_page = 24, 8, 4, 8
        cg_n, cg_gen, cg_K = 3, 40, 4
    eng_f = Engine(model, max_seq=fs_len + max(fs_gen, cg_gen) + 24,
                   backend=backend)
    rng = np.random.RandomState(21)
    fs_ids = rng.randint(0, cfg.vocab_size,
                         size=(fs_len,)).astype(np.int32)

    def fork_run():
        sched = ContinuousScheduler(eng_f, batch=fs_n, chunk=4,
                                    paged=True, page=fs_page)
        t0 = time.perf_counter()
        out = sched.run([Request(rid="f", ids=fs_ids, gen_len=fs_gen,
                                 seed=0, n=fs_n)])
        return time.perf_counter() - t0, sched.stats(), out

    def seq_run():
        sched = ContinuousScheduler(eng_f, batch=fs_n, chunk=4,
                                    paged=True, page=fs_page,
                                    prefix_cache=False)
        t0 = time.perf_counter()
        out = sched.run([Request(rid=k, ids=fs_ids, gen_len=fs_gen,
                                 seed=k) for k in range(fs_n)])
        return time.perf_counter() - t0, out

    fork_run(), seq_run()                  # warm the slot programs
    fk_dt, fk_st, fk_out = fork_run()
    sq_dt, sq_out = seq_run()
    assert all(np.array_equal(fk_out[("f", k)], sq_out[k])
               for k in range(fs_n)), "fork streams diverged"
    _emit_json({
        "metric": "parallel_sampling_prefill_skip_frac",
        "value": round(fk_st["prefill_skip_frac"], 4),
        "unit": "frac",
        "n": fs_n,
        "fork_wall_s": round(fk_dt, 4),
        "sequential_wall_s": round(sq_dt, 4),
        "fork_shared_pages": fk_st["fork_shared_pages"],
        "fork_cow_breaks": fk_st["fork_cow_breaks"],
        "prompt_tokens": fs_len,
        "backend": jax.default_backend(),
    })

    cg_schema = {"type": "object", "properties": {
        "answer": {"type": "boolean"},
        "count": {"type": "integer", "maxDigits": 3}}}
    cg_g = GrammarSpec.from_json_schema(cg_schema,
                                        byte_vocab(cfg.vocab_size))

    def cg_reqs(grammar):
        r = np.random.RandomState(22)
        return [Request(rid=i,
                        ids=r.randint(0, cfg.vocab_size,
                                      size=(fs_len,)).astype(np.int32),
                        gen_len=cg_gen, grammar=grammar)
                for i in range(cg_n)]

    def cg_run(grammar, K):
        mk = lambda: ContinuousScheduler(eng_f, batch=cg_n, chunk=4,
                                         paged=True, page=fs_page,
                                         spec=K)
        mk().run(cg_reqs(grammar))         # warm the programs
        sched = mk()
        t0 = time.perf_counter()
        out = sched.run(cg_reqs(grammar))
        dt = time.perf_counter() - t0
        total = sum(len(t) for t in out.values())
        return total / dt, sched.stats()

    cg_on, st_on = cg_run(cg_g, cg_K)      # jump-ahead: forced drafts
    cg_off, _ = cg_run(cg_g, 0)            # masked, one token/forward
    cg_base, _ = cg_run(None, 0)           # unconstrained baseline
    _emit_json({
        "metric": "constrained_decode_tok_per_s",
        "value": round(cg_on, 2),
        "unit": "tok/s",
        "jump_ahead": True, "spec": cg_K,
        "jump_off_tok_per_s": round(cg_off, 2),
        "unconstrained_tok_per_s": round(cg_base, 2),
        "jump_ahead_tokens": st_on.get("jump_ahead_tokens"),
        "grammar_mask_tokens": st_on.get("grammar_mask_tokens"),
        "requests": cg_n,
        "backend": jax.default_backend(),
    })

    # --- fleet traffic-plane rows (triton_dist_tpu/fleet/): (a) the
    # prefix-aware router over 2 replicas on a shared-system-prompt
    # workload — the row's value is router_prefix_hit_frac with the
    # fleet-wide prefill_skip_frac (and the round-robin arm's, which
    # scatters the warm prefixes) alongside; (b) a mixed-SLO storm on
    # a deliberately tight fleet (batch=1 per replica, no queue) —
    # interactive p99 TTFT with SLO-aware shedding (batch gives way)
    # vs the class-blind round-robin arm where interactive queues
    # behind batch occupants. Both arms serve IDENTICAL request sets;
    # warm-up storm first, measured storm second.
    from triton_dist_tpu.fleet import FleetRouter, InprocReplica
    from triton_dist_tpu.serving import ByteTokenizer

    fl_tok = ByteTokenizer(cfg.vocab_size)
    fl_gen = 16 if on_tpu else 8

    def fleet(policy, tag, **kw):
        reps = [InprocReplica(f"{tag}{i}", eng_f, fl_tok, batch=2,
                              chunk=4, paged=True, page=fs_page)
                for i in range(2)]
        return FleetRouter(reps, fl_tok, policy=policy, **kw)

    fl_prompts = ["You are a helpful TPU fleet. " + q
                  for q in ("alpha?", "beta!", "gamma.", "delta;")]
    fl_skip = {}
    for policy in ("prefix", "rr"):
        router = fleet(policy, f"b_{policy}")
        try:
            for i, p in enumerate(fl_prompts):       # warm + measure
                router.run(p, gen_len=fl_gen, seed=i)
            fl_skip[policy] = (
                router.fleet_cache_stats()["prefill_skip_frac"],
                router.stats()["router_prefix_hit_frac"])
        finally:
            router.shutdown()
    _emit_json({
        "metric": "fleet_prefix_hit_frac",
        "value": round(fl_skip["prefix"][1], 4),
        "unit": "frac",
        "replicas": 2,
        "prefill_skip_frac": round(fl_skip["prefix"][0], 4),
        "rr_prefill_skip_frac": round(fl_skip["rr"][0], 4),
        "requests": len(fl_prompts),
        "backend": jax.default_backend(),
    })

    def storm(router):
        """A batch wave EXCEEDING fleet capacity (6 long requests onto
        2 batch=1/queue=1 replicas) takes every slot and queue, then 3
        short interactive ones arrive; returns (sorted interactive
        first-chunk TTFTs (s), interactive requests served). TTFT is
        the FIRST chunk only, and the served count rides along so an
        arm that drops interactive work can't flatter its latency
        tail — a dropped request contributes no TTFT sample but shows
        up as a miss. The overload is the point: shedding only pays
        when there is MORE batch than capacity — the shed keeps the
        queues free for interactive, where the class-blind arm's
        queues stay full of batch backlog."""
        import threading as _th
        ttfts = []
        served = [0]

        def client(slo, i, g):
            t0 = time.perf_counter()
            first = True
            for msg in router.stream(f"storm {slo} {i}",
                                     gen_len=g, seed=i, slo=slo):
                if msg.get("done"):
                    if slo == "interactive" \
                            and msg.get("error") is None:
                        served[0] += 1
                    break
                if first and slo == "interactive":
                    ttfts.append(time.perf_counter() - t0)
                    first = False

        bts = [_th.Thread(target=client,
                          args=("batch", i, 4 * fl_gen))
               for i in range(6)]
        its = [_th.Thread(target=client,
                          args=("interactive", 6 + i, fl_gen))
               for i in range(3)]
        for t in bts:
            t.start()
        time.sleep(0.1)
        for t in its:
            t.start()
        for t in bts + its:
            t.join(timeout=600)
        return sorted(ttfts), served[0]

    storm_p99 = {}
    storm_served = {}
    for arm, policy, kw in (
            ("router", "prefix", dict(shed_inflight=2,
                                      busy_retries=40)),
            ("rr", "rr", dict(busy_retries=40))):
        router = FleetRouter(
            [InprocReplica(f"s_{arm}{i}", eng_f, fl_tok, batch=1,
                           chunk=4, paged=True, page=fs_page,
                           max_queue=1) for i in range(2)],
            fl_tok, policy=policy, **kw)
        try:
            storm(router)                            # warm
            ts, n_served = storm(router)             # measure
            storm_p99[arm] = (ts[min(len(ts) - 1,
                                     int(0.99 * len(ts)))] * 1e3
                              if ts else -1.0)
            storm_served[arm] = n_served
        finally:
            router.shutdown()
    _emit_json({
        "metric": "router_storm_p99_ttft_ms",
        "value": round(storm_p99["router"], 2),
        "unit": "ms",
        "slo": "interactive",
        "interactive_served": storm_served["router"],
        "round_robin_p99_ttft_ms": round(storm_p99["rr"], 2),
        "round_robin_interactive_served": storm_served["rr"],
        "replicas": 2,
        "backend": jax.default_backend(),
    })

    # --- fleet HA rows (triton_dist_tpu/fleet/ha.py): (a) failover
    # recovery — kill the active router mid-stream (chaos
    # kill_routers arm) and report the journal-splice promotion
    # latency the client rode through without seeing an error; (b)
    # exactly-once dedup — resubmit K COMPLETED request_ids and report
    # the fraction answered straight from the dedup window (1.0 means
    # every retry cost zero re-served tokens). Both rows ride the same
    # capture + history ledger, so bench_compare gates failover
    # latency (ms, lower better) and dedup coverage (frac, higher
    # better) like any other metric.
    from triton_dist_tpu.fleet import ReplicatedRouter
    from triton_dist_tpu.runtime.chaos import FaultInjector

    ha_fault = FaultInjector(kill_routers=[1])
    ha_pair = ReplicatedRouter(
        [InprocReplica(f"ha{i}", eng_f, fl_tok, batch=2, chunk=4,
                       paged=True, page=fs_page) for i in range(2)],
        fl_tok, fault=ha_fault)
    try:
        ha_ids = [f"bench-ha-{i}" for i in range(4)]
        for i, rid in enumerate(ha_ids):         # first serve (the
            ha_pair.run(f"ha bench {i}",         # kill fires in req 0)
                        gen_len=fl_gen, seed=i, request_id=rid)
        ha_st = ha_pair.stats()
        _emit_json({
            "metric": "failover_recovery_ms",
            "value": ha_st["last_failover_ms"],
            "unit": "ms",
            "failover_count": ha_st["failover_count"],
            "replayed_requests": ha_st["replayed_requests"],
            "journal_entries": ha_st.get("journal_entries"),
            "backend": jax.default_backend(),
        })
        for rid in ha_ids:                       # exactly-once retry
            ha_pair.run("retry ignored", gen_len=fl_gen, seed=0,
                        request_id=rid)
        ha_hits = ha_pair.stats()["dedup_hits"] - ha_st["dedup_hits"]
        _emit_json({
            "metric": "dedup_hit_rate",
            "value": round(ha_hits / len(ha_ids), 4),
            "unit": "frac",
            "retries": len(ha_ids),
            "dedup_hits": ha_hits,
            "backend": jax.default_backend(),
        })
    finally:
        ha_pair.shutdown()

    # roofline rows: per-kernel achieved/SOL fractions from
    # tools/perf_report, into the same capture + history ledger so
    # bench_compare --strict gates on same-backend roofline
    # regressions. TDTPU_BENCH_SOLFRAC: "0" disables, "all" runs the
    # full report, default runs the GATE_OPS subset. Its human-readable
    # printout goes to stderr so stdout stays one JSON line per row.
    solfrac_mode = os.environ.get("TDTPU_BENCH_SOLFRAC", "")
    if solfrac_mode != "0":
        import contextlib

        from triton_dist_tpu.tools.perf_report import (
            GATE_OPS, run_report, sol_frac_rows)
        with contextlib.redirect_stdout(sys.stderr):
            rep = run_report(
                only=None if solfrac_mode == "all" else GATE_OPS)
        for row in sol_frac_rows(rep):
            _emit_json(row)


def main():
    _bench()
    return 0


if __name__ == "__main__":
    sys.exit(main())
