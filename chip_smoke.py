"""Bring-up proof: serve Qwen3-1.7B on one TPU chip through the entry
points a user calls, and check what comes out.

    python chip_smoke.py              # one chip; fails where there is none
    python chip_smoke.py --chips 4    # the TP=4 phase only (four-chip host)
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse   # tiny model, CPU

One process, top to bottom: `initialize_distributed()` ->
`AutoLLM.from_config(qwen3_1p7b())` -> `Engine` -> `TokenServer(paged=True,
prefix_cache=True)` -> `request_stream` clients on threads. Two phases:

1. engine differential: `Engine(backend="flash")` (the Pallas kernels)
   against `Engine(backend="xla")` on the same weights and prompts —
   prefill logits, then every step of a 16-step decode through the
   cache, teacher-forced by the reference's greedy token;
2. server: six concurrent clients, three sharing a 64-token prefix;
   every stream must end `done` with the asked count and no error, the
   prefix cache must hit, and the page pool must balance at the end.

Any failed check or exception ends the run non-zero; nothing is caught
and downgraded. Earlier output lines are logs of this run (one JSON
object each), not measurements. The LAST line is the verdict the driver
reads: {"ok": true, "device": {"platform", "kind", "count"}}.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import threading
import time

# --- what the run is held to -------------------------------------------
BATCH, PAGE, N_CLIENTS, N_SHARING = 8, 16, 6, 3


@dataclasses.dataclass(frozen=True)
class Sizes:
    prompt_len: int      # engine differential: prompt tokens per row
    decode_steps: int    # ... and decode steps through the cache
    max_seq: int         # cache capacity per slot
    gen_len: int         # server: tokens asked per client


ON_CHIP = Sizes(prompt_len=128, decode_steps=16, max_seq=512, gen_len=32)
# the CPU rehearsal interprets every Pallas kernel; same control flow,
# fewer positions, so it fits a tier-1 test
REHEARSAL = Sizes(prompt_len=32, decode_steps=4, max_seq=128, gen_len=8)

# Logit tolerance, as a fraction of the reference's largest |logit|.
# The two backends run the same equations with different accumulation
# orders (online-softmax tiles against one softmax, bf16 rounding
# between kernels), so they agree to the activation dtype's epsilon
# (bf16 2^-8, f32 2^-23) compounded through the layer stack, not
# bitwise. At these widths in bf16 the CPU interpreter shows 1.0% of
# the largest logit after 2 layers and 1.7% after 8 (growing like the
# root of the depth, so ~3% at 28). A wrong mask, offset or page
# mapping replaces logits by unrelated ones: an error of the logits'
# own spread, which over a [8, 151936] block peaks above the largest
# logit itself. 8% sits between the two.
TOL_REL = {"bfloat16": 8e-2, "float32": 1e-4}


class SmokeFailure(RuntimeError):
    """A check of this script did not hold."""


def _log(**fields) -> None:
    print(json.dumps(fields), flush=True)


def _require(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def _timed(fn):
    """(result, wall seconds) with the device work finished inside."""
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, round(time.perf_counter() - t0, 3)


class CompileLog:
    """What JAX reports about compilation, per phase: how many programs
    went to the backend compiler and for how long, and what the
    persistent cache did. take() returns the tally and starts anew."""

    _EVENTS = {
        "/jax/core/compile/jaxpr_trace_duration": "trace_s",
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
        "/jax/core/compile/backend_compile_duration": "backend_compile_s",
        "/jax/compilation_cache/cache_hits": "cache_hits",
        "/jax/compilation_cache/cache_misses": "cache_misses",
    }

    def __init__(self):
        import jax.monitoring
        self._lock = threading.Lock()
        self._tally: dict = {}
        jax.monitoring.register_event_listener(
            lambda event, **kw: self._add(event, 1))
        jax.monitoring.register_event_duration_secs_listener(
            lambda event, secs, **kw: self._add(event, secs))

    def _add(self, event: str, amount: float) -> None:
        key = self._EVENTS.get(event)
        if key is None:
            return
        with self._lock:                 # the server compiles on a thread
            self._tally[key] = self._tally.get(key, 0) + amount
            if key == "backend_compile_s":
                self._tally["programs"] = self._tally.get("programs", 0) + 1
                if amount >= 1.0:
                    self._tally["programs_over_1s"] = \
                        self._tally.get("programs_over_1s", 0) + 1

    def take(self) -> dict:
        with self._lock:
            out, self._tally = self._tally, {}
        return {k: round(v, 3) for k, v in sorted(out.items())}


def _versions() -> dict:
    import importlib.metadata as md
    import jax
    import jaxlib
    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = None
    return {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "libtpu": libtpu}


def _peak_bytes(device):
    stats = device.memory_stats()          # None on backends without it
    return None if stats is None else stats.get("peak_bytes_in_use")


def _compare(ref, got, tol_rel: float) -> dict:
    """Max logit error of `got` against `ref` ([B, V] float32), and
    greedy-token agreement in every row whose top-2 margin in the
    reference is wider than the tolerance (random-init logits are
    nearly flat, so a bare token equality would fail on rounding, not
    on bugs)."""
    import numpy as np
    ref = np.asarray(ref, np.float32)
    got = np.asarray(got, np.float32)
    _require(ref.shape == got.shape, f"shapes {ref.shape} {got.shape}")
    _require(bool(np.isfinite(got).all()), "non-finite logits")
    tol = tol_rel * float(np.abs(ref).max())
    err = float(np.abs(got - ref).max())
    top2 = np.sort(ref, axis=-1)[:, -2:]
    decided = (top2[:, 1] - top2[:, 0]) > tol
    agree = ref.argmax(-1) == got.argmax(-1)
    return {"max_err": err, "tol": tol,
            "rms_err": float(np.sqrt(np.mean((got - ref) ** 2))),
            "ref_std": float(ref.std()),
            "rows_same_token": int(agree.sum()),
            "rows_decided": int(decided.sum()),
            "rows_disagree": int((decided & ~agree).sum())}


def _differential(ref_eng, eng, ids, steps: int, tol_rel: float,
                  label: str, compiles: CompileLog) -> None:
    """`eng` against `ref_eng` (same model object): prefill, then
    `steps` single-token decode scans through each engine's own cache.
    Both are fed the reference's logits, so both take the reference's
    greedy token and one early near-tie cannot void the later steps."""
    model = eng.model
    (lx, cx), ref_first = _timed(lambda: ref_eng.prefill(ids))
    (lf, cf), first = _timed(lambda: eng.prefill(ids))
    _, second = _timed(lambda: eng.prefill(ids))
    worst = _compare(lx, lf, tol_rel)
    _log(phase=f"{label}.prefill", backend=eng.backend,
         ref_backend=ref_eng.backend, first_call_s=first,
         second_call_s=second, ref_first_call_s=ref_first,
         compile=compiles.take(), **worst)
    _require(worst["max_err"] <= worst["tol"]
             and not worst["rows_disagree"], f"{label} prefill: {worst}")
    step_s = []
    for t in range(steps):
        (_, lx_next, cx), _ = _timed(lambda: ref_eng._decode_scan(
            model, lx, cx, gen_len=1))
        (_, lf, cf), dt = _timed(lambda: eng._decode_scan(
            model, lx, cf, gen_len=1))
        lx = lx_next
        step_s.append(dt)
        cmp = _compare(lx, lf, tol_rel)
        _require(cmp["max_err"] <= cmp["tol"]
                 and not cmp["rows_disagree"],
                 f"{label} decode step {t}: {cmp}")
        if cmp["max_err"] >= worst["max_err"]:
            worst = cmp
    _log(phase=f"{label}.decode", steps=steps, first_call_s=step_s[0],
         second_call_s=step_s[1], compile=compiles.take(),
         last_step=cmp, worst_step=worst)


def _serve(eng, vocab: int, gen_len: int, seed: int,
           compiles: CompileLog) -> None:
    """TokenServer on an ephemeral port, served from a thread; the
    clients are threads of this process too (a chip belongs to one
    process)."""
    import numpy as np
    from triton_dist_tpu.serving import (ByteTokenizer, TokenServer,
                                         request_stream)
    rng = np.random.RandomState(seed)

    def text(n):                            # n printable ASCII bytes
        return bytes(rng.randint(32, 127, size=n).tolist()).decode()

    # 64 shared bytes = four whole pages; every prompt is 80 tokens, so
    # admission compiles two suffix buckets (80 cold, 16 after a hit)
    shared = text(4 * PAGE)
    prompts = [shared + text(PAGE) for _ in range(N_SHARING)] + \
        [text(5 * PAGE) for _ in range(N_CLIENTS - N_SHARING)]

    srv = TokenServer(eng, ByteTokenizer(vocab), batch=BATCH, paged=True,
                      prefix_cache=True, page=PAGE)
    failures: list = []

    def guarded(fn, *args):
        try:
            fn(*args)
        except BaseException as e:           # re-raised on the main thread
            failures.append(e)

    results: dict = {}

    def client(i):
        toks, done = [], None
        for msg in request_stream(srv.host, srv.port, prompts[i],
                                  gen_len=gen_len, timeout=900.0):
            if msg.get("done"):
                done = msg
                break
            toks.extend(msg["token_ids"])
        results[i] = (toks, done)

    server = threading.Thread(
        target=guarded, name="smoke-server",
        args=(lambda: srv.serve_forever(max_requests=N_CLIENTS),))
    clients = [threading.Thread(target=guarded, args=(client, i),
                                name=f"smoke-client-{i}")
               for i in range(N_CLIENTS)]
    t0 = time.perf_counter()
    server.start()
    for c in clients:
        c.start()
    for c in clients:
        c.join(timeout=1000)
    srv.stop()
    server.join(timeout=60)
    wall = round(time.perf_counter() - t0, 3)
    if failures:
        raise failures[0]
    alive = [t.name for t in [server, *clients] if t.is_alive()]
    _require(not alive, f"threads still running: {alive}")

    for i in range(N_CLIENTS):
        toks, done = results.get(i, ([], None))
        _require(done is not None, f"client {i}: no done message")
        _require("error" not in done, f"client {i}: {done}")
        _require(len(toks) == gen_len == done["n_tokens"],
                 f"client {i}: {len(toks)} tokens, done={done}")
        _require(all(0 <= t < vocab for t in toks),
                 f"client {i}: token out of vocab")
    st = srv.stats()
    pool = srv.sched.slots.prefix.pool
    keep = ("admissions", "hits", "hit_rate", "prompt_tokens",
            "prefill_tokens_skipped", "prefill_skip_frac", "evictions",
            "pages_in_use", "pages_free", "pages_outstanding",
            "host_ms_per_poll", "tokens_generated", "polls")
    _log(phase="server", wall_s=wall, clients=N_CLIENTS,
         gen_len=gen_len, pool_pages=pool.num_pages,
         compile=compiles.take(),
         stats={k: st[k] for k in keep if k in st})
    _require(st["hits"] >= 1 and st["prefill_tokens_skipped"] > 0,
             f"prefix cache never hit: {st['hits']} hits")
    _require(pool.available + pool.outstanding == pool.num_pages,
             f"page pool leaks: {pool.available} + {pool.outstanding} "
             f"!= {pool.num_pages}")


def _tune_stores_absent() -> None:
    """A populated tune store under the user's home would change block
    shapes from outside git; this run must not be steered by one."""
    import os
    from triton_dist_tpu.tools import sweep, tune
    for path in (sweep.default_store_path(), tune.default_cache_path()):
        exists = os.path.exists(path)
        _log(tune_store=path, exists=exists)
        _require(not exists, f"tune store {path} exists: this run's "
                 "block shapes would come from outside the checkout")


def _engines_differ(devices, cfg, sz: Sizes, seed: int, backend: str,
                    label: str, compiles: CompileLog):
    """The model over a TP mesh of `devices`, and its `backend` engine
    held to its `xla` engine on one seeded prompt batch. Returns the
    `backend` engine."""
    import jax
    import numpy as np
    from triton_dist_tpu.models import AutoLLM, Engine
    from triton_dist_tpu.runtime import initialize_distributed

    ctx = initialize_distributed({"tp": len(devices)}, devices=devices)
    model, init_s = _timed(
        lambda: AutoLLM.from_config(cfg, ctx.mesh, seed=seed))
    weight_bytes = sum(x.nbytes for x in jax.tree.leaves(model)
                       if hasattr(x, "nbytes"))
    _log(phase=f"{label}.init", seconds=init_s, weight_bytes=weight_bytes,
         layers=cfg.num_layers, hidden=cfg.hidden_size,
         vocab=cfg.vocab_size, dtype=cfg.dtype, compile=compiles.take(),
         compile_cache_dir=jax.config.jax_compilation_cache_dir)
    if len(devices) > 1:
        _log_placement(model, devices)

    eng = Engine(model, max_seq=sz.max_seq, backend=backend)
    xla = Engine(model, max_seq=sz.max_seq, backend="xla")
    ids = np.random.RandomState(seed).randint(
        0, cfg.vocab_size, size=(BATCH, sz.prompt_len)).astype(np.int32)
    _differential(xla, eng, ids, sz.decode_steps, TOL_REL[cfg.dtype],
                  label, compiles)
    _log(phase=f"{label}.memory",
         peak_bytes_in_use=[_peak_bytes(d) for d in devices])
    return eng


def _log_placement(model, devices) -> None:
    """Where each weight lives: code that has only seen one chip may put
    everything on the first. Every array must reach all the devices,
    and the TP projections must be split, not copied."""
    import jax
    want = sorted(d.id for d in devices)
    split = 0
    for path, x in jax.tree_util.tree_leaves_with_path(model):
        if not isinstance(x, jax.Array):
            continue
        name = jax.tree_util.keystr(path)
        shards = x.addressable_shards
        ids = sorted(s.device.id for s in shards)
        _require(ids == want, f"{name} lives on devices {ids}, "
                 f"not on {want}")
        shard_shape = tuple(shards[0].data.shape)
        split += shard_shape != tuple(x.shape)
        if ".layers[" not in name or ".layers[0]" in name:
            _log(weight=name, shape=list(x.shape),
                 shard_shape=list(shard_shape), device_ids=ids,
                 spec=str(x.sharding.spec))
    per_layer = 4                  # w_qkv, w_o, w_gate_up, w_down
    _require(split >= per_layer * model.config.num_layers,
             f"only {split} arrays are split across the mesh")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run ONLY the TP=4 phase (gemm_ar against "
                         "xla on one four-device mesh)")
    ap.add_argument("--rehearse", action="store_true",
                    help="run the same code at tiny_qwen3(1) on the CPU "
                         "(last line then says platform cpu)")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: no TPU (jax.devices()[0] is {dev.platform} "
              f"{dev.device_kind!r}); this run needs the chip",
              file=sys.stderr)
        return 1

    from triton_dist_tpu.models.config import qwen3_1p7b, tiny_qwen3
    from triton_dist_tpu.runtime import interpret_mode
    _log(**_versions(), platform=dev.platform, kind=dev.device_kind,
         devices=len(devices), rehearse=args.rehearse, seed=args.seed)
    if dev.platform == "tpu":
        _require(interpret_mode() is False,
                 "Pallas kernels would be interpreted on the chip")
    _tune_stores_absent()

    _require(len(devices) >= args.chips,
             f"--chips {args.chips} on a host with {len(devices)}")
    cfg, sz = ((tiny_qwen3(args.chips), REHEARSAL) if args.rehearse
               else (qwen3_1p7b(), ON_CHIP))
    t0 = time.perf_counter()
    compiles = CompileLog()
    if args.chips == 1:
        flash = _engines_differ([dev], cfg, sz, args.seed, "flash",
                                "engine", compiles)
        _serve(flash, cfg.vocab_size, sz.gen_len, args.seed, compiles)
        _log(phase="server.memory", peak_bytes_in_use=_peak_bytes(dev))
    else:
        # TP=4 only: the fused GEMM+allreduce comm kernels against
        # XLA's own collectives, same mesh, same weights. If the fused
        # kernels fail the run fails; there is no switch to `xla`.
        _engines_differ(devices[:4], cfg, sz, args.seed, "gemm_ar", "tp4",
                        compiles)
    _log(phase="total", seconds=round(time.perf_counter() - t0, 3))

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": args.chips}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
