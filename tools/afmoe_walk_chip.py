"""Trinity-Mini's two decode walks alone on one TPU chip, at the shapes of
the cell `trinity-mini-ep8.agent-saturated` (a sibling of
tools/paged_walk_chip.py, which holds the other cells' shapes):

    chiprun -- python tools/afmoe_walk_chip.py            # check, then time
    chiprun -- python tools/afmoe_walk_chip.py time --slots 32
    JAX_PLATFORMS=cpu python tools/afmoe_walk_chip.py --rehearse

- the RING walk of a window layer: the contiguous `flash_decode` over
  rings `[64, 4, 2048, 128]` (K and V), 32 query heads, every slot's
  ring full;
- the PAGED walk of a full layer: `flash_decode_paged(fused=True)` over
  one plane `[NP, 8, 16, 128]` (a page's K rows, then its V rows), 64
  slots at contexts drawn uniformly from 8,192 to 10,240 of max_seq
  12,288.

`check`: each against a float32 softmax over the same rows, with an
empty and a short slot in the batch. `time`: ms a call over chained
calls in one jitted scan (6 ring walks and 2 paged walks: a decode
step's worth), beside the least time of the bytes each must read at the
chip's 819 GB/s. One JSON line a reading, `{"ok": true, ...}` last.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

PAGE, D, HQ, HKV = 16, 128, 32, 4
HBM = 819e9


def _say(**kw):
    print(json.dumps(kw), flush=True)


def _inputs(slots, window, max_seq, lo, hi, seed=0):
    import jax
    import jax.numpy as jnp
    ks = jax.random.split(jax.random.key(seed), 5)
    bf = jnp.bfloat16
    maxp = max_seq // PAGE
    lens = np.random.default_rng(seed).integers(lo, hi + 1, slots)
    lens[0], lens[1] = 0, min(5, hi)      # an empty and a short slot
    table = 1 + np.arange(slots * maxp, dtype=np.int32).reshape(slots, maxp)
    return dict(
        q=jax.random.normal(ks[0], (slots, 1, HQ, D), bf),
        ring_k=jax.random.normal(ks[1], (slots, HKV, window, D), bf),
        ring_v=jax.random.normal(ks[2], (slots, HKV, window, D), bf),
        pool=jax.random.normal(ks[3], (slots * maxp + 1, 2 * HKV, PAGE, D),
                               bf),
        table=jnp.asarray(table), lens=jnp.asarray(lens, jnp.int32))


def _walks(window):
    import jax.numpy as jnp
    from triton_dist_tpu.kernels.flash_attn import flash_decode
    from triton_dist_tpu.kernels.paged_kv import flash_decode_paged

    def ring(x, q):
        wl = jnp.minimum(x["lens"], window)
        return flash_decode(q, x["ring_k"], x["ring_v"], jnp.max(wl),
                            kv_lens=wl)

    def paged(x, q):
        return flash_decode_paged(q, x["pool"], None, x["table"],
                                  jnp.max(x["lens"]), kv_lens=x["lens"],
                                  fused=True)

    return ring, paged


def check(x, window):
    import jax
    import jax.numpy as jnp
    from triton_dist_tpu.kernels.flash_attn import attention_cached_ref
    from triton_dist_tpu.kernels.paged_kv import gather_pages
    ring, paged = _walks(window)
    wl = jnp.minimum(x["lens"], window)
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    rows = gather_pages(x["pool"], x["table"])
    want = {
        "ring": attention_cached_ref(f32(x["q"]), f32(x["ring_k"]),
                                     f32(x["ring_v"]), wl),
        "paged": attention_cached_ref(f32(x["q"]), f32(rows[:, :HKV]),
                                      f32(rows[:, HKV:]), x["lens"])}
    live = np.asarray(x["lens"]) > 0
    ok = True
    for name, fn in (("ring", ring), ("paged", paged)):
        got = np.asarray(f32(jax.jit(fn)(x, x["q"])))[live]
        err = float(np.abs(got - np.asarray(want[name])[live]).max())
        ok &= err < 0.03 and bool(np.isfinite(got).all())
        _say(phase="check", walk=name, max_err=err)
    return ok


def timed(x, window, calls):
    import jax
    ring, paged = _walks(window)
    lens = np.asarray(x["lens"])
    need = {"ring": float(np.minimum(lens, window).sum()),
            "paged": float(lens.sum())}
    for name, fn, n in (("ring", ring, calls[0]), ("paged", paged, calls[1])):
        def chain(x, q, fn=fn, n=n):
            def body(q, _):
                return fn(x, q).astype(q.dtype), None
            return jax.lax.scan(body, q, None, length=n)[0]
        run = jax.jit(chain)
        jax.block_until_ready(run(x, x["q"]))
        best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            jax.block_until_ready(run(x, x["q"]))
            best = min(best, time.perf_counter() - t0)
        least = need[name] * 2 * HKV * D * 2 / HBM
        _say(phase="time", walk=name, calls=n, ms_a_call=1e3 * best / n,
             least_ms=1e3 * least, positions=need[name],
             roofline_pct=100.0 * least / (best / n))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("phases", nargs="*", default=["check", "time"])
    ap.add_argument("--slots", type=int, default=64)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny shapes, for a CPU run before the chip call")
    a = ap.parse_args()
    import jax
    from triton_dist_tpu.runtime import interpret_mode
    dev = jax.devices()[0]
    if a.rehearse:
        slots, window, max_seq, lo, hi, calls = 4, 32, 128, 40, 100, (2, 1)
    else:
        assert dev.platform == "tpu" and interpret_mode() is False, dev
        slots, window, max_seq, lo, hi, calls = (a.slots, 2048, 12288, 8192,
                                                 10240, (6, 2))
    x = _inputs(slots, window, max_seq, lo, hi)
    ok = True
    if "check" in a.phases:
        ok = check(x, window)
    if "time" in a.phases:
        timed(x, window, calls)
    _say(ok=bool(ok), device={"platform": dev.platform,
                              "kind": dev.device_kind})
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
