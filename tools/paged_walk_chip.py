"""The paged decode walk (kernels/paged_kv.py) alone on one TPU chip:
what the CPU interpreter cannot show of it.

    chiprun -- python tools/paged_walk_chip.py                 # all three
    chiprun -- python tools/paged_walk_chip.py time --block-w 4 8 16
    JAX_PLATFORMS=cpu python tools/paged_walk_chip.py --rehearse

Three phases, each one JSON line per reading, `{"ok": true, ...}` last:

- `check`: the kernel against a float32 softmax over the gathered pages
  at the served shapes, with empty streams (kv length 0: a parked or
  budget-starved slot) as whole grid steps and beside live streams,
  plain decode and `q_lens` windows. On the chip a copy that nothing
  waits for leaves its bytes on a DMA semaphore, and the next step then
  computes on a buffer its own copies have not filled: the interpreter
  completes every copy at its start and cannot see that.
- `mixed`: chunked prefill (`ContinuousScheduler(prefill_budget=...)`,
  `step_mixed`) with more prompts in flight than the budget feeds, so
  slots parked at position 0 ride the walk with window 0; the Pallas
  backend's streams against the XLA backend's under the same chunking,
  beside the same pair under whole-prompt admission (the control).
- `time`: ms a call, 28 chained calls in one jitted scan (a decode
  step's worth on Qwen3-1.7B), page 16, at the three cells' shapes
  (`SHAPES`): one chip's 32 slots x 8 heads x 2 rows and a TP=4 chip's
  32 x 2 x 8, lengths uniform 256..640 over 128 table columns; and
  Phi-4's 64 slots x 10 paired heads x 4 rows, lengths 2,048..3,300
  over 256 columns. PERF.md's tables of forms, of W and of ns a copy
  were read from this.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

# run as a script from anywhere: the package lives one directory up
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

PAGE, D, CALLS = 16, 128, 28
# what a chip holds of every slot in each cell, and the lengths `time`
# draws there (the cells' contexts)
SHAPES = {"1chip": dict(B=32, Hkv=8, Hq=16),
          "tp4": dict(B=32, Hkv=2, Hq=16),
          "phi4": dict(B=64, Hkv=10, Hq=40)}
TIMED = {"1chip": (128, 256, 640), "tp4": (128, 256, 640),
         "phi4": (256, 2048, 3300)}      # table columns, shortest, longest
TOL = 0.03      # bf16 pools and P against float32: ~0.01 at these sizes


def _log(**fields) -> None:
    print(json.dumps(fields), flush=True)


def _build(rng, B, Hkv, Hq, maxp, lens, S=1):
    """Random pools behind a shuffled table (page 0 unused): a page is
    a slot's PAGE positions for all of its Hkv heads."""
    import jax
    import jax.numpy as jnp
    NP = B * maxp + 1
    # made on the device: Phi-4's two pools are 1.3 GB
    pk, pv = (jax.random.normal(
        jax.random.PRNGKey(rng.randint(1 << 30)), (NP, Hkv, PAGE, D),
        jnp.bfloat16) * 0.5 for _ in range(2))
    table = jnp.asarray(
        1 + rng.permutation(NP - 1).reshape(B, maxp), jnp.int32)
    q = jnp.asarray(rng.randn(B, S, Hq, D) * 0.5, jnp.bfloat16)
    return q, pk, pv, table, jnp.asarray(lens, jnp.int32)


def _reference(q, pk, pv, table, lens, q_lens=None):
    from triton_dist_tpu.kernels.flash_attn import attention_cached_ref
    import jax.numpy as jnp
    B, maxp = table.shape

    def gather(pool):       # [B, maxp, Hkv, PAGE, D] -> [B, Hkv, T, D]
        return pool[table].transpose(0, 2, 1, 3, 4).reshape(
            B, pool.shape[1], maxp * PAGE, D)

    k, v = gather(pk), gather(pv)
    return attention_cached_ref(q.astype(jnp.float32), k, v, lens,
                                q_lens=q_lens)


def check(rehearse: bool) -> None:
    import jax
    import jax.numpy as jnp
    from triton_dist_tpu.kernels.paged_kv import flash_decode_paged
    rng = np.random.RandomState(30)
    for name, kw in SHAPES.items():
        B = kw["B"]
        maxp = 16 if rehearse else TIMED[name][0]
        cap = maxp * PAGE
        for windows in (False, True):
            S = 4 if windows else 1
            lens = rng.randint(cap // 8, cap // 3, size=B)
            qls = rng.randint(1, S + 1, size=B)
            # empty slots: the first, the last, a lone one, and a run
            # that is a whole grid step at every W (4 at TP=4) with
            # more of them beside live slots
            for b in (0, 5, 6, 7, 8, 9, 10, 11, 17, B - 1):
                lens[b], qls[b] = 0, (0 if windows else 1)
            lens[12], lens[18] = 1, cap
            qls = np.minimum(qls, lens)     # a window lies inside its stream
            q, pk, pv, table, kvl = _build(rng, maxp=maxp, lens=lens, S=S,
                                           **kw)
            ql = jnp.asarray(qls, jnp.int32) if windows else None
            out = jax.jit(lambda *a: flash_decode_paged(
                a[0], a[1], a[2], a[3], None, kv_lens=a[4], q_lens=ql))(
                    q, pk, pv, table, kvl)
            live = lens > 0
            out = np.asarray(out, np.float32)
            ref = np.asarray(_reference(q, pk, pv, table, kvl, ql))
            if windows:     # rows past a slot's window are discarded
                rows = np.arange(S)[None] < qls[:, None]
                out, ref = out * rows[..., None, None], \
                    ref * rows[..., None, None]
            err = float(np.abs(out[live] - ref[live]).max())
            _log(phase="check", shape=name, windows=windows,
                 empty_slots=int((~live).sum()), max_err=err, tol=TOL,
                 empty_rows_zero=bool((out[~live] == 0).all()))
            assert err <= TOL and (out[~live] == 0).all(), (name, err)


def mixed(rehearse: bool) -> None:
    import jax
    from triton_dist_tpu.models import AutoLLM, ContinuousScheduler, Engine
    from triton_dist_tpu.models.config import qwen3_1p7b, tiny_qwen3
    from triton_dist_tpu.models.scheduler import Request
    from triton_dist_tpu.runtime import initialize_distributed
    # the 1.7B's widths (8 kv heads a slot, so W = 1: a parked slot is
    # a whole grid step), two layers of it
    cfg = (tiny_qwen3(1) if rehearse
           else dataclasses.replace(qwen3_1p7b(), num_layers=2))
    L, g, budget, max_seq = (16, 6, 2, 64) if rehearse else (72, 12, 16, 256)
    ctx = initialize_distributed({"tp": 1}, devices=jax.devices()[:1])
    model = AutoLLM.from_config(cfg, ctx.mesh, seed=30)

    def run(backend, prefill_budget):
        rng = np.random.RandomState(3)
        reqs = [Request(rid=i, gen_len=g, seed=100 + i,
                        ids=rng.randint(0, cfg.vocab_size, size=(L + 5 * i,)
                                        ).astype(np.int32))
                for i in range(4)]
        sched = ContinuousScheduler(
            Engine(model, max_seq=max_seq, backend=backend), batch=4,
            chunk=4, paged=True, page=PAGE, prefill_budget=prefill_budget)
        return sched.run(reqs), sched.stats()

    # random-init logits are nearly flat, so rounding flips a greedy
    # token now and then and the stream then goes its own way: the
    # control (whole-prompt admission, no window ever empty) says how
    # often. Stale K/V would make every stream unrelated from its start
    agreed = {}
    for label, pb in (("control", None), ("mixed", budget)):
        (ref, _), (got, st) = run("xla", pb), run("flash", pb)
        same = {rid: int((np.asarray(got[rid]) == np.asarray(ref[rid])).sum())
                for rid in ref}
        asked = sum(len(np.asarray(v)) for v in ref.values())
        agreed[label] = sum(same.values())
        _log(phase=label, backend="flash", ref_backend="xla", tokens=asked,
             tokens_same=agreed[label], per_request=same,
             prefill_budget=pb, max_prefill_tokens_per_poll=st.get(
                 "max_prefill_tokens_per_poll"))
        assert all(len(got[r]) == len(ref[r]) for r in ref)
    assert agreed["mixed"] >= agreed["control"] // 2, agreed


def _ms_per_call(args, block_w):
    import jax
    import jax.numpy as jnp
    from triton_dist_tpu.kernels.paged_kv import flash_decode_paged

    def chain(q, pk, pv, table, lens):
        def body(qc, _):
            o = flash_decode_paged(qc, pk, pv, table, None, kv_lens=lens,
                                   block_w=block_w)
            return (qc + o * jnp.bfloat16(0.01)).astype(qc.dtype), ()
        return jax.lax.scan(body, q, None, length=CALLS)[0]

    f = jax.jit(chain)
    t0 = time.perf_counter()
    f(*args).block_until_ready()
    first = time.perf_counter() - t0
    ts = []
    for _ in range(5):
        t0 = time.perf_counter()
        f(*args).block_until_ready()
        ts.append(time.perf_counter() - t0)
    return min(ts) / CALLS * 1e3, float(np.median(ts)) / CALLS * 1e3, first


def timing(widths, lens) -> None:
    rng = np.random.RandomState(0)
    for name, kw in SHAPES.items():
        maxp, lo, hi = TIMED[name]
        if lens:
            lo, hi = lens
        args = _build(rng, maxp=maxp,
                      lens=rng.randint(lo, hi + 1, size=kw["B"]), **kw)
        for w in widths or [None]:
            w = w or None           # 0 = the kernel's own pick
            if w is not None and kw["B"] % w:
                continue
            mn, med, first = _ms_per_call(args, w)
            _log(phase="time", shape=name, block_w=w, lens=[lo, hi],
                 ms_min=round(mn, 4), ms_med=round(med, 4),
                 first_call_s=round(first, 1))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("phases", nargs="*", default=["check", "mixed", "time"],
                    help="of check, mixed, time (default: all)")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes for the CPU interpreter; no timing")
    ap.add_argument("--block-w", type=int, nargs="*", default=None,
                    help="slots per grid step to time (default, and 0: "
                         "the kernel's own pick)")
    ap.add_argument("--lens", type=int, nargs=2, default=None,
                    help="shortest and longest context to time (default: "
                         "each shape's own, the cells')")
    args = ap.parse_args()
    import jax
    dev = jax.devices()[0]
    _log(device=str(dev), kind=dev.device_kind)
    if not args.rehearse:
        assert dev.platform == "tpu", f"not a TPU: {dev}"
    if args.rehearse:
        for kw in SHAPES.values():
            kw["B"] = 20
    if "check" in args.phases:
        check(args.rehearse)
    if "mixed" in args.phases:
        mixed(args.rehearse)
    if "time" in args.phases and not args.rehearse:
        timing(args.block_w, args.lens)
    _log(ok=True, device={"platform": dev.platform, "kind": dev.device_kind})


if __name__ == "__main__":
    main()
