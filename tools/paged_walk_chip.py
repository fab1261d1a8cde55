"""The paged decode walk (kernels/paged_kv.py) alone on one TPU chip:
what the CPU interpreter cannot show of it.

    chiprun -- python tools/paged_walk_chip.py                 # all three
    chiprun -- python tools/paged_walk_chip.py check time gmm --shapes mla
    chiprun -- python tools/paged_walk_chip.py time --block-w 4 8 16
    chiprun -- python tools/paged_walk_chip.py append --shapes 1chip tp4
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
  over 256 columns; and the latent walk's 128 slots x 1 latent head x
  128 query rows (`mla`: rows of 640 lanes, values their first 512),
  lengths 1,024..3,000. PERF.md's tables of forms, of W and of ns a
  copy were read from this.
- `gmm` (only when asked for): the expert layer's local stage
  (layers/ep_moe.py `expert_rows`: both ragged grouped GEMMs) at 16
  held experts of the published 7168 x 2 x 2048 / 2048 x 7168, for a
  decode tick's 1,024 pair rows (~64 of them on held experts) and a
  1,024-token admission's 8,192 (~512), against a plain einsum on the
  rows that landed, then ms a call over 8 chained calls.
- `sa` (only when asked for): learned sparse attention at Keye-VL-2.0's
  published widths and the cell's shapes (32 slots, contexts 16,384 to
  20,400 of max_seq 20,480, 2,048 selected): the decode step's three
  pieces (the index scores of the per-slot index plane, the selection,
  the paged walk under it: K and V in one plane of 8 head rows) checked
  against float32 with a short and an empty slot in the batch, then ms
  a call each over 6 chained calls (a step's six layers) beside the
  least time of their bytes; and one layer's 16,384-token admission
  (`SA_Attn.prefill`: index scores, selection and attention, 256 query
  rows at a time), ms a call. Beside them, what the layouts and the
  selection were chosen against: the index scores over keys PADDED to
  128 lanes (256 B a position where the packed plane holds the
  published 128) and at other blocks than the kernel's 2,048
  positions, the plane's append packed (a row read, merged and written)
  and as a plain row write, the same walk over TWO planes (K and V
  pages apart, two copies a page) under the same set, and the same set
  by `jax.lax.top_k` and a scatter to the mask.
- `append` (only when asked for): the decode append of the two-plane
  pool (`append_page_rows`, one launch for K and V) against two
  scatters (`set_page_rows`) at the served pools, bitwise but the trash
  page, then us a call over 28 chained calls on donated planes.
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
          "phi4": dict(B=64, Hkv=10, Hq=40),
          "mla": dict(B=128, Hkv=1, Hq=128, d=640, v_cols=512)}
TIMED = {"1chip": (128, 256, 640), "tp4": (128, 256, 640),
         "phi4": (256, 2048, 3300),      # table columns, shortest, longest
         "mla": (256, 1024, 3000)}
TOL = 0.03      # bf16 pools and P against float32: ~0.01 at these sizes


def _log(**fields) -> None:
    print(json.dumps(fields), flush=True)


def _build(rng, B, Hkv, Hq, maxp, lens, S=1, d=D, v_cols=None):
    """Random pools behind a shuffled table (page 0 unused): a page is
    a slot's PAGE positions for all of its Hkv heads. v_cols: a latent
    pool (one plane, no V: pv is None)."""
    import jax
    import jax.numpy as jnp
    NP = B * maxp + 1
    # made on the device: Phi-4's two pools are 1.3 GB
    pk, pv = (jax.random.normal(
        jax.random.PRNGKey(rng.randint(1 << 30)), (NP, Hkv, PAGE, d),
        jnp.bfloat16) * 0.5 for _ in range(2))
    table = jnp.asarray(
        1 + rng.permutation(NP - 1).reshape(B, maxp), jnp.int32)
    q = jnp.asarray(rng.randn(B, S, Hq, d) * (0.5 if d == D else 0.2),
                    jnp.bfloat16)
    return (q, pk, None if v_cols else pv, table,
            jnp.asarray(lens, jnp.int32))


def _reference(q, pk, pv, table, lens, q_lens=None, v_cols=None):
    from triton_dist_tpu.kernels.flash_attn import attention_cached_ref
    from triton_dist_tpu.kernels.paged_kv import gather_pages
    import jax.numpy as jnp
    k = gather_pages(pk, table)
    v = k if pv is None else gather_pages(pv, table)
    out = attention_cached_ref(q.astype(jnp.float32), k, v, lens,
                               q_lens=q_lens)
    return out[..., :v_cols] if v_cols else out


def check(rehearse: bool) -> None:
    import jax
    import jax.numpy as jnp
    from triton_dist_tpu.kernels.paged_kv import flash_decode_paged
    rng = np.random.RandomState(30)
    for name, kw in SHAPES.items():
        B, v_cols = kw["B"], kw.get("v_cols")
        maxp = 16 if rehearse else TIMED[name][0]
        cap = maxp * PAGE
        # a latent pool serves plain decode only (query windows ride
        # spec decode and chunked prefill, both refused for it)
        for windows in ((False,) if v_cols else (False, True)):
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
                a[0], a[1], a[2], a[3], None, kv_lens=a[4], q_lens=ql,
                v_cols=v_cols))(q, pk, pv, table, kvl)
            live = lens > 0
            out = np.asarray(out, np.float32)
            ref = np.asarray(_reference(q, pk, pv, table, kvl, ql,
                                        v_cols))
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


def _ms_per_call(args, block_w, v_cols=None):
    import jax
    import jax.numpy as jnp
    from triton_dist_tpu.kernels.paged_kv import flash_decode_paged

    def chain(q, pk, pv, table, lens):
        def body(qc, _):
            o = flash_decode_paged(qc, pk, pv, table, None, kv_lens=lens,
                                   block_w=block_w, v_cols=v_cols)
            o = jnp.pad(o, ((0, 0),) * 3 + ((0, qc.shape[-1]
                                             - o.shape[-1]),))
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
            mn, med, first = _ms_per_call(args, w, kw.get("v_cols"))
            _log(phase="time", shape=name, block_w=w, lens=[lo, hi],
                 ms_min=round(mn, 4), ms_med=round(med, 4),
                 first_call_s=round(first, 1))


def gmm(rehearse: bool) -> None:
    """The expert layer's local stage alone (module docstring)."""
    import jax
    import jax.numpy as jnp
    from triton_dist_tpu.layers.ep_moe import expert_rows
    E, Dm, F, K = (4, 128, 128, 4) if rehearse else (16, 7168, 2048, 8)
    ks = jax.random.split(jax.random.PRNGKey(36), 6)
    dt = jnp.float32 if rehearse else jnp.bfloat16
    wgu = (jax.random.normal(ks[0], (E, Dm, 2 * F), jnp.float32)
           * Dm ** -0.5).astype(dt)
    wd = (jax.random.normal(ks[1], (E, F, Dm), jnp.float32)
          * F ** -0.5).astype(dt)
    for label, T in (("decode", 16 if rehearse else 128),
                     ("admit", 64 if rehearse else 1024)):
        x = jax.random.normal(ks[2], (T, Dm), jnp.float32).astype(dt)
        R = T * K
        # a pair lands on a held expert with probability 1/16
        eid = jnp.where(jax.random.uniform(ks[3], (R,)) < 1 / 16,
                        jax.random.randint(ks[4], (R,), 0, E), E)
        src = jnp.arange(R) // K
        f = jax.jit(lambda x, eid, a, b: expert_rows(x, src, eid, a, b))
        y = np.asarray(f(x, eid, wgu, wd), np.float32)
        eid_h = np.asarray(eid)
        held = eid_h < E
        rows = np.nonzero(held)[0]
        want = np.zeros((len(rows), Dm), np.float32)
        for e in range(E):          # an expert at a time: 117 MB in f32
            at = np.nonzero(eid_h[rows] == e)[0]
            if not len(at):
                continue
            xe = x[np.asarray(src)[rows[at]]].astype(jnp.float32)
            g, u = jnp.split(xe @ wgu[e].astype(jnp.float32), 2, axis=-1)
            h = (g * jax.nn.sigmoid(g) * u).astype(dt).astype(jnp.float32)
            want[at] = np.asarray(h @ wd[e].astype(jnp.float32))
        err = float(np.abs(y[rows] - np.asarray(want)).max())
        zero = bool((y[~held] == 0).all())
        tol = 1e-4 if rehearse else 0.06
        _log(phase="gmm_check", shape=label, pair_rows=R,
             pairs_held=int(held.sum()), max_err=err, tol=tol,
             other_rows_zero=zero)
        assert err <= tol and zero, (label, err)
        if rehearse:
            continue

        def chain(x, eid, a, b):
            def body(xc, _):
                y = expert_rows(xc, src, eid, a, b)
                return (xc + y.reshape(T, K, Dm).sum(1)
                        * jnp.bfloat16(0.01)).astype(xc.dtype), ()
            return jax.lax.scan(body, x, None, length=8)[0]

        fc = jax.jit(chain)
        fc(x, eid, wgu, wd).block_until_ready()
        ts = []
        for _ in range(5):
            t0 = time.perf_counter()
            fc(x, eid, wgu, wd).block_until_ready()
            ts.append(time.perf_counter() - t0)
        _log(phase="gmm_time", shape=label, pair_rows=R,
             pairs_held=int(held.sum()), ms_min=round(min(ts) / 8 * 1e3, 4),
             ms_med=round(float(np.median(ts)) / 8 * 1e3, 4),
             weights_ms_at_819=round(
                 1e3 * (wgu.nbytes + wd.nbytes) / 819e9, 4))


def sa(rehearse: bool) -> None:
    """Learned sparse attention's kernels alone (module docstring)."""
    import jax
    import jax.numpy as jnp
    from triton_dist_tpu.kernels import sparse_attn
    from triton_dist_tpu.kernels.paged_kv import (flash_decode_paged,
                                                  gather_pages)
    from triton_dist_tpu.kernels.sparse_attn import (append_index_keys,
                                                     index_plane_shape,
                                                     index_scores,
                                                     index_scores_ref,
                                                     pack_index_keys,
                                                     select_topk)
    from triton_dist_tpu.layers.sparse_attn import SA_Attn
    B, Hq, Hkv, Hi, di, topk = (4, 8, 2, 4, 16, 32) if rehearse else (
        32, 32, 4, 16, 64, 2048)
    max_seq, lo, hi = (256, 100, 250) if rehearse else (20480, 16384, 20400)
    P_ = 128 if rehearse else 16384
    dt = jnp.float32 if rehearse else jnp.bfloat16
    maxp = max_seq // PAGE
    NP = B * maxp + 1
    rng = np.random.RandomState(39)
    ks = jax.random.split(jax.random.PRNGKey(39), 8)
    kv = (jax.random.normal(ks[0], (NP, 2 * Hkv, PAGE, D), jnp.float32)
          * 0.5).astype(dt)
    # the index plane: every slot's keys in one run, two a row
    rows_i, lanes = index_plane_shape(max_seq, di)
    keys = jax.random.normal(ks[1], (B, 2 * rows_i, di), jnp.float32
                             ).astype(dt)
    pack = jax.jit(jax.vmap(lambda k: pack_index_keys(
        k, *index_plane_shape(max_seq, k.shape[-1]))))
    ix = pack(keys)
    table = jnp.asarray(1 + rng.permutation(NP - 1).reshape(B, maxp),
                        jnp.int32)
    lens = rng.randint(lo, hi + 1, size=B)
    lens[1], lens[2] = topk // 2, 0         # nothing to choose; empty
    lens = jnp.asarray(lens, jnp.int32)
    q = (jax.random.normal(ks[2], (B, 1, Hq, D), jnp.float32) * 0.5
         ).astype(dt)
    qi = jax.random.normal(ks[3], (B, Hi, di), jnp.float32).astype(dt)
    w = jax.random.normal(ks[4], (B, Hi), jnp.float32)
    scale = di ** -0.5 * Hi ** -0.5

    def slot_scores(qi, w, ix, n):
        return index_scores(qi[:, None], w[:, None], ix, n,
                            scale=scale)[:, 0]

    score = jax.jit(slot_scores)
    choose = jax.jit(lambda sc, n: select_topk(
        sc, jnp.arange(sc.shape[1])[None] < n[:, None], topk))
    walk = jax.jit(lambda q, kv, t, n, sel: flash_decode_paged(
        q, kv, None, t, None, kv_lens=n, fused=True, sel=sel))
    sc = score(qi, w, ix, lens)
    sel = choose(sc, lens)
    out = walk(q, kv, table, lens, sel)
    # against float32, a slot at a time (a slot's rows are 84 MB)
    live = np.asarray(lens) > 0
    worst = {"index": 0.0, "walk": 0.0}
    sets_equal = True
    for b in range(B):
        n = int(lens[b])
        if not n:
            continue
        ref = index_scores_ref(qi[b:b + 1].astype(jnp.float32),
                               w[b:b + 1], keys[b, :n].astype(jnp.float32),
                               scale=scale)[0]
        got = sc[b, :n]
        worst["index"] = max(worst["index"],
                             float(jnp.abs(got - ref).max()))
        # the selection of the kernel's own scores, by a sort
        want = np.zeros((n,), bool)
        want[np.argsort(-np.asarray(got), kind="stable")[:topk]] = True
        sets_equal &= bool((np.asarray(sel[b, :n]) == want).all()) \
            and not bool(np.asarray(sel[b, n:]).any())
        rows = gather_pages(kv, table[b:b + 1])[0, :, :n].astype(
            jnp.float32)
        qg = q[b, 0].reshape(Hkv, Hq // Hkv, D).astype(jnp.float32)
        s_ = jnp.einsum("hrd,htd->hrt", qg, rows[:Hkv]) * D ** -0.5
        p = jax.nn.softmax(jnp.where(sel[b, :n][None, None], s_,
                                     -jnp.inf), -1)
        o = jnp.einsum("hrt,htd->hrd", p, rows[Hkv:]).reshape(Hq, D)
        worst["walk"] = max(worst["walk"], float(jnp.abs(
            out[b, 0].astype(jnp.float32) - o).max()))
    empty_zero = bool((np.asarray(out, np.float32)[~live] == 0).all())
    _log(phase="sa_check", slots=B, lens=[lo, hi], topk=topk,
         index_max_err=worst["index"], walk_max_err=worst["walk"],
         sets_equal=sets_equal, empty_rows_zero=empty_zero, tol=TOL)
    assert worst["index"] <= TOL and worst["walk"] <= TOL and sets_equal \
        and empty_zero, worst

    # one layer's admission: the scan of 256-row blocks
    attn = SA_Attn.init(
        *[(jax.random.normal(k, sh, jnp.float32) * sh[0] ** -0.5
           ).astype(dt) for k, sh in zip(jax.random.split(ks[5], 4), (
               (256, Hq * D), (256, Hkv * D), (256, Hkv * D),
               (Hq * D, 256)))],
        jnp.ones((D,), dt), jnp.ones((D,), dt),
        *[(jax.random.normal(k, sh, jnp.float32) * 256 ** -0.5).astype(dt)
          for k, sh in zip(jax.random.split(ks[6], 3), (
              (256, Hi * di), (256, di), (256, Hi)))],
        n_heads=Hq, n_kv_heads=Hkv, head_dim=D, idx_heads=Hi, idx_dim=di,
        topk=topk)
    u = jax.random.normal(ks[7], (P_, 256), jnp.float32).astype(dt)
    pos = jnp.arange(P_)
    ang = lambda d: (pos[:, None] * (1e7 ** (  # noqa: E731
        -jnp.arange(0, d, 2) / d))[None]).astype(jnp.float32)
    rope = (jnp.cos(ang(D)), jnp.sin(ang(D)))
    rope_i = (jnp.cos(ang(di)), jnp.sin(ang(di)))
    pids = table[0][:P_ // PAGE]
    outs = {}
    for impl in ("flash",) + (("ref",) if rehearse else ()):
        f = jax.jit(lambda u, kv, ix, impl=impl: attn.prefill(
            u, rope, rope_i, kv, ix, pids, 0, impl=impl)[0])
        t0 = time.perf_counter()
        outs[impl] = f(u, kv, ix).block_until_ready()
        first = time.perf_counter() - t0
        t0 = time.perf_counter()
        f(u, kv, ix).block_until_ready()
        _log(phase="sa_prefill", impl=impl, tokens=P_,
             ms=round(1e3 * (time.perf_counter() - t0), 3),
             first_call_s=round(first, 1))
    if rehearse:
        err = float(jnp.abs(outs["flash"] - outs["ref"]).max())
        _log(phase="sa_prefill_check", max_err=err)
        assert err < 1e-4, err

    def ms(fn, *args):
        """ms a call over 6 chained calls (the first argument is fed
        back, perturbed by the result)."""
        def chain(*a):
            def body(x, _):
                y = fn(x, *a[1:])
                # (rows past a slot's end are whatever was there)
                y = jnp.sum(jnp.where(jnp.isfinite(y), y, 0).astype(
                    jnp.float32))
                return (x + (y * 1e-9).astype(x.dtype)), ()
            return jax.lax.scan(body, a[0], None, length=6)[0]
        f = jax.jit(chain)
        f(*args).block_until_ready()
        ts = []
        for _ in range(1 if rehearse else 5):
            t0 = time.perf_counter()
            f(*args).block_until_ready()
            ts.append(time.perf_counter() - t0)
        return round(min(ts) / 6 * 1e3, 4), round(
            float(np.median(ts)) / 6 * 1e3, 4)

    # the admission's three pieces, one 256-row block against 16,384
    # keys (the scan runs 64 of them a layer)
    from triton_dist_tpu.kernels.sparse_attn import selected_attention
    M = min(256, P_)
    qb = (jax.random.normal(ks[2], (M, Hq, D), jnp.float32) * 0.5
          ).astype(dt)
    qib = jax.random.normal(ks[3], (M, Hi, di), jnp.float32).astype(dt)
    wb = jax.random.normal(ks[4], (M, Hi), jnp.float32)
    kib = pack_index_keys(
        jax.random.normal(ks[5], (P_, di), jnp.float32).astype(dt),
        rows_i, lanes)[None]
    kT, vT = (jax.random.normal(k_, (Hkv, P_, D), jnp.float32) * 0.5
              for k_ in jax.random.split(ks[6], 2))
    kT, vT = kT.astype(dt), vT.astype(dt)
    c1 = jnp.int32(P_)
    causal = jnp.arange(P_)[None] <= (P_ - M + jnp.arange(M))[:, None]

    def block_scores(qi, w, k):
        return index_scores(qi[None], w[None], k, c1[None],
                            scale=scale)[0, :, :P_]

    scb = block_scores(qib, wb, kib)
    selb = select_topk(scb, causal, topk)
    for name, t in (
            ("index_block", ms(block_scores, qib, wb, kib)),
            ("select_block", ms(lambda sc: select_topk(
                sc, causal, topk), scb)),
            ("attend_block", ms(lambda q, k, v, sel: selected_attention(
                q, k, v, sel, c1, scale=D ** -0.5), qb, kT, vT, selb))):
        _log(phase="sa_time", kernel=name, ms_min=t[0], ms_med=t[1],
             rows=M, keys=P_)

    def top_k_mask(sc, valid):
        """The same set by `jax.lax.top_k` and a scatter to the mask
        (the plain reference's way), for timing beside `select_topk`."""
        _, idx = jax.lax.top_k(jnp.where(valid, sc, -jnp.inf),
                               min(topk, sc.shape[1]))
        return jnp.zeros(sc.shape, bool).at[
            jnp.arange(sc.shape[0])[:, None], idx].set(True) & valid

    live_cols = jnp.arange(sc.shape[1])[None] < lens[:, None]
    assert bool((top_k_mask(sc, live_cols) == sel).all())
    for name, t in (
            ("lax_top_k_block", ms(lambda sc: top_k_mask(sc, causal), scb)),
            ("lax_top_k", ms(lambda sc, n: top_k_mask(
                sc, jnp.arange(sc.shape[1])[None] < n[:, None]), sc, lens))):
        _log(phase="sa_time", kernel=name, ms_min=t[0], ms_med=t[1],
             shape=list(scb.shape if "block" in name else sc.shape))

    # the same walk over TWO planes (PagedSlotCache's layout: K and V
    # pages of Hkv heads each, two copies a page) under the same set
    pk, pv = kv[:, :Hkv], kv[:, Hkv:]
    two = jax.jit(lambda q, pk, pv, t, n, sel: flash_decode_paged(
        q, pk, pv, t, None, kv_lens=n, sel=sel))(q, pk, pv, table, lens,
                                                 sel)
    err2 = float(jnp.abs(two[np.asarray(live)].astype(jnp.float32)
                         - out[np.asarray(live)].astype(jnp.float32)).max())
    _log(phase="sa_check", two_plane_vs_fused_max_err=err2)
    assert err2 <= TOL, err2

    ctx = float(np.asarray(lens).sum())
    att = float(np.minimum(np.asarray(lens), topk).sum())
    for bw in (None,) + (() if rehearse else (4, 8)):
        try:
            t = ms(lambda q, pk, pv, t, n, sel, bw=bw: flash_decode_paged(
                q, pk, pv, t, None, kv_lens=n, sel=sel, block_w=bw),
                q, pk, pv, table, lens, sel)
        except Exception as e:     # a W the chip's VMEM refuses
            _log(phase="sa_time", kernel=f"two_plane_walk_w{bw}",
                 refused=repr(e)[:300])
            continue
        _log(phase="sa_time", kernel=f"two_plane_walk_w{bw or 'pick'}",
             ms_min=t[0], ms_med=t[1], positions_in_context=ctx,
             positions_attended=att)
    # the index scores: the plane as served, then (the record of why
    # it is packed, and why its block is what it is) the key PADDED to
    # 128 lanes, a row of two still: 256 B a position; and other blocks
    # over the same bytes (random keys: any layout is as good)
    k128 = pack(jnp.pad(keys, ((0, 0), (0, 0), (0, 128 - di))))
    q128 = jnp.pad(qi, ((0, 0), (0, 0), (0, 128 - di)))
    assert bool((score(q128, w, k128, lens) == sc)[live_cols].all())
    index_times = [("index_walk", ms(slot_scores, qi, w, ix, lens)),
                   ("index_walk_padded", ms(slot_scores, q128, w, k128,
                                            lens))]
    served = sparse_attn.INDEX_BLOCK
    for blk in () if rehearse else (1024, 4096):
        sparse_attn.INDEX_BLOCK = blk
        try:
            index_times.append((f"index_walk_block{blk}", ms(
                slot_scores, qi, w, ix, lens)))
        finally:
            sparse_attn.INDEX_BLOCK = served

    def per_append(fn, plane):
        """ms an append over 24 chained ones, the plane carried."""
        ki = keys[:, 0]

        def chain(plane, pos):
            return jax.lax.scan(
                lambda c, _: ((fn(c[0], ki, c[1]), c[1] + 1), ()),
                (plane, pos), None, length=24)[0][0]
        f = jax.jit(chain, donate_argnums=0)
        plane = f(plane, lens).block_until_ready()
        ts = []
        for _ in range(1 if rehearse else 5):
            t0 = time.perf_counter()
            plane = f(plane, lens).block_until_ready()
            ts.append(time.perf_counter() - t0)
        return round(min(ts) / 24 * 1e3, 5)

    for name, t in (
            ("append_packed", per_append(append_index_keys, ix + 0)),
            ("append_row", per_append(
                lambda plane, ki, pos: plane.at[jnp.arange(B), pos].set(
                    jnp.pad(ki, ((0, 0), (0, 128 - di)))),
                jnp.zeros((B, max_seq, 128), dt)))):
        _log(phase="sa_time", kernel=name, ms_min=t, slots=B)
    for name, t, least in tuple(
            (name, t, ctx * di * 2) for name, t in index_times) + (
            ("select", ms(lambda sc, n: select_topk(
                sc, jnp.arange(sc.shape[1])[None] < n[:, None], topk),
                sc, lens), 0.0),
            ("selected_walk", ms(lambda q, kv, t, n, sel: flash_decode_paged(
                q, kv, None, t, None, kv_lens=n, fused=True, sel=sel),
                q, kv, table, lens, sel), att * 2 * Hkv * D * 2)) + tuple(
            (f"selected_walk_w{bw}", ms(
                lambda q, kv, t, n, sel, bw=bw: flash_decode_paged(
                    q, kv, None, t, None, kv_lens=n, fused=True, sel=sel,
                    block_w=bw), q, kv, table, lens, sel),
             att * 2 * Hkv * D * 2) for bw in (1, 4) if not rehearse):
        _log(phase="sa_time", kernel=name, ms_min=t[0], ms_med=t[1],
             positions_in_context=ctx, positions_attended=att,
             least_ms_at_819=round(1e3 * least / 819e9, 4),
             whole_context_ms_at_819=round(
                 1e3 * ctx * 2 * Hkv * D * 2 / 819e9, 4)
             if name == "selected_walk" else None)


def append(rehearse: bool) -> None:
    """The decode append of the two-plane pool (append_page_rows, one
    aliased launch for K and V) against set_page_rows on each plane, at
    the served pools: bitwise on every page but the trash page 0, with
    three retired slots on it, rows at 0 and 15; then ms a call over
    28 chained calls on donated planes (a step's layers), each form."""
    import jax
    import jax.numpy as jnp
    from triton_dist_tpu.kernels.paged_kv import (append_page_rows,
                                                  set_page_rows)

    def scatter(pk, pv, pidx, r, k, v):
        return (set_page_rows(pk, pidx, r, k), set_page_rows(pv, pidx, r, v))

    rng = np.random.RandomState(44)
    for name in ("1chip", "tp4"):
        if name not in SHAPES:
            continue
        B, Hkv = SHAPES[name]["B"], SHAPES[name]["Hkv"]
        NP = B * (8 if rehearse else TIMED[name][0]) + 1
        pk, pv = (jax.random.normal(jax.random.PRNGKey(i), (NP, Hkv, PAGE,
                                    D), jnp.bfloat16) for i in range(2))
        k, v = (jnp.asarray(rng.randn(B, Hkv, D), jnp.bfloat16)
                for _ in range(2))
        pidx = np.concatenate([1 + rng.permutation(NP - 1)[:B - 3], [0] * 3])
        r = rng.randint(0, PAGE, B)
        r[:4] = [0, 0, 15, 15]
        pidx, r = jnp.asarray(pidx, jnp.int32), jnp.asarray(r, jnp.int32)
        got = jax.jit(append_page_rows)(pk, pv, pidx, r, k, v)
        want = jax.jit(scatter)(pk, pv, pidx, r, k, v)
        same = [bool(jnp.array_equal(a[1:], b[1:]))
                for a, b in zip(got, want)]
        _log(phase="append", shape=name, bitwise=same)
        assert all(same), name
        if rehearse:
            continue
        for form, fn in (("kv_append", append_page_rows),
                         ("scatter", scatter)):
            def chain(pk, pv, pidx, r, k, v, fn=fn):
                def body(c, i):
                    return fn(*c, pidx, (r + i) % PAGE, k + i.astype(k.dtype),
                              v), ()
                return jax.lax.scan(body, (pk, pv), jnp.arange(CALLS))[0]
            f = jax.jit(chain, donate_argnums=(0, 1))
            pk, pv = f(pk, pv, pidx, r, k, v)
            jax.block_until_ready(pk)
            ts = []
            for _ in range(5):
                t0 = time.perf_counter()
                pk, pv = f(pk, pv, pidx, r, k, v)
                jax.block_until_ready(pk)
                ts.append(time.perf_counter() - t0)
            _log(phase="append_time", shape=name, form=form,
                 us_min=round(min(ts) / CALLS * 1e6, 2),
                 us_med=round(float(np.median(ts)) / CALLS * 1e6, 2))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("phases", nargs="*", default=["check", "mixed", "time"],
                    help="of check, mixed, time, gmm, sa, append (default: "
                         "the first three)")
    ap.add_argument("--shapes", nargs="*", default=None,
                    help="of " + ", ".join(SHAPES) + " (default: all)")
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
    for name in list(SHAPES):
        if args.shapes and name not in args.shapes:
            del SHAPES[name]
    if args.rehearse:
        for kw in SHAPES.values():
            kw["B"] = 20
    if "check" in args.phases:
        check(args.rehearse)
    if "mixed" in args.phases:
        mixed(args.rehearse)
    if "time" in args.phases and not args.rehearse:
        timing(args.block_w, args.lens)
    if "gmm" in args.phases:
        gmm(args.rehearse)
    if "sa" in args.phases:
        sa(args.rehearse)
    if "append" in args.phases:
        append(args.rehearse)
    _log(ok=True, device={"platform": dev.platform, "kind": dev.device_kind})


if __name__ == "__main__":
    main()
