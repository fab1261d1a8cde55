"""Serving hot-loop lint (tdcheck checker 4).

The scheduler's poll loop has two structural perf contracts the
bitwise suites guard only dynamically (test_overlap's compile-counter
churn guard; the coalesced-readback design of DecodeSlots._fetch):

1. **no recompile-key churn**: every poll must reuse the SAME jitted
   program objects with the SAME trace — a fresh partial per poll, a
   non-deterministic static arg, or a trace-time fresh collective id
   silently turns the decode tick into a compile storm. Checked two
   ways: `_jit_programs` must be process-cached (calling it twice with
   one configuration returns the IDENTICAL program dict), and every
   decode-tick program must trace DETERMINISTICALLY (two traces at the
   canonical shapes hash identically).
2. **no host transfer inside the decode tick**: the tick programs must
   contain no callback/infeed/outfeed primitive — any host hop inside
   the jitted tick serializes the device pipeline the overlap
   scheduler exists to fill (the PR-7 zero-host-transfer contract).
   The ONE legitimate host readback is the scheduler's coalesced
   device_get in `_fetch`, which lives outside the programs.

Everything here is trace-only (jax.make_jaxpr): the full lint over the
canonical tiny-model program set compiles nothing and runs in seconds.
"""

from __future__ import annotations

import hashlib
from typing import Callable, Dict, Optional, Tuple

from triton_dist_tpu.analysis import Report, eqn_src, iter_eqns

_HERE = "triton_dist_tpu/analysis/hotloop.py"

# host-transfer primitives: anything here inside a decode-tick program
# is a poll-loop stall (jax spells callbacks differently across
# versions; match on substring)
_HOST_PRIM_MARKERS = ("callback", "infeed", "outfeed")


def jaxpr_hash(fn, *args, **kwargs) -> str:
    """Stable hash of fn's trace at these shapes (the recompile key's
    observable body)."""
    import jax
    jaxpr = jax.make_jaxpr(lambda *a: fn(*a, **kwargs))(*args)
    return hashlib.sha256(str(jaxpr).encode()).hexdigest()


def check_host_transfers(fn, args, kwargs, subject: str,
                         report: Report) -> None:
    import jax
    jaxpr = jax.make_jaxpr(lambda *a: fn(*a, **kwargs))(*args)
    for eqn in iter_eqns(jaxpr.jaxpr):
        name = eqn.primitive.name
        if any(m in name for m in _HOST_PRIM_MARKERS):
            report.add(
                "error", eqn_src(eqn), subject,
                f"host transfer inside a decode-tick program: "
                f"primitive '{name}' round-trips to the host every "
                f"tick, serializing the device pipeline the overlap "
                f"scheduler hides host work behind — move it to the "
                f"scheduler's coalesced readback (_fetch) or drop it")


def check_trace_determinism(fn, args, kwargs, subject: str,
                            report: Report) -> None:
    h1 = jaxpr_hash(fn, *args, **kwargs)
    h2 = jaxpr_hash(fn, *args, **kwargs)
    if h1 != h2:
        report.add(
            "error", _HERE + ":check_trace_determinism", subject,
            f"recompile-key churn: two traces of this program at "
            f"identical shapes differ ({h1[:12]} vs {h2[:12]}) — "
            f"something trace-impure (a fresh collective id, a counter "
            f"baked as a literal, an id()-keyed branch) retraces every "
            f"poll and recompiles the tick")


def check_program_cache_identity(report: Report) -> None:
    """_jit_programs must hand back the SAME dict (and program
    objects) for one configuration — jax's executable cache keys on
    the callable object, so fresh wrappers mean a compile per poll."""
    from triton_dist_tpu.models.engine import _jit_programs
    key = ("flash", "greedy", (0.0, 0, 1.0), "auto")
    a = _jit_programs(*key)
    b = _jit_programs(*key)
    if a is not b:
        report.add(
            "error", "triton_dist_tpu/models/engine.py:_jit_programs",
            "_jit_programs",
            "program-set factory is not process-cached: two calls "
            "with one configuration returned distinct dicts — every "
            "engine construction recompiles the whole slot-program "
            "family")
    else:
        for name in a:
            if a[name] is not b[name]:
                report.add(
                    "error",
                    "triton_dist_tpu/models/engine.py:_jit_programs",
                    name,
                    "program object is rebuilt per call: jax's "
                    "executable cache keys on the callable, so this "
                    "program recompiles per engine")


def tick_bodies(engine) -> Dict[str, Callable]:
    """The engine's own greedy program set (engine._jit_programs),
    each program unwrapped to the function its jit traces: which
    function serves a tick, and with which static arguments, is
    decided there and only there."""
    from triton_dist_tpu.models.engine import _jit_programs, _params_key
    progs = _jit_programs(engine.backend, "greedy",
                          _params_key(engine._sample_params),
                          engine.prefill_backend)
    return {name: p.__wrapped__ for name, p in progs.items()}


def canonical_programs(engine, batch: int = 2
                       ) -> Dict[str, Tuple]:
    """(fn, args, kwargs) per decode-tick program at canonical tiny
    shapes — the hot-loop surface ContinuousScheduler polls. The
    functions are tick_bodies'; the names listed are the programs a
    poll dispatches, and the arguments are what each takes."""
    import jax
    import jax.numpy as jnp
    model = engine.model
    V = model.config.vocab_size
    B = batch
    body = tick_bodies(engine)
    cache = engine.make_slot_cache(B)
    pcache = engine.make_paged_slot_cache(B)
    logits0 = jnp.zeros((B, V), jnp.float32)
    pos = jnp.zeros((B,), jnp.int32)
    active = jnp.ones((B,), bool)
    tokens = jnp.zeros((B, 2), jnp.int32)
    q_lens = jnp.ones((B,), jnp.int32)
    prefilling = jnp.zeros((B,), bool)
    keys = jax.random.split(jax.random.PRNGKey(0), B)
    ids = jnp.zeros((2,), jnp.int32)

    # restore_pages' payload shapes come from the gather's avals
    gshape = jax.eval_shape(body["gather_pages"], model, pcache, ids)
    hk = jnp.zeros(gshape[0].shape, gshape[0].dtype)
    hv = jnp.zeros(gshape[1].shape, gshape[1].dtype)
    scan = {"gen_len": 2}
    calls = {
        "slot_scan": ((model, logits0, cache, pos, active), scan),
        "paged_slot_scan": ((model, logits0, pcache, pos, active),
                            scan),
        "slot_verify": ((model, cache, pos, active, tokens, q_lens),
                        {}),
        "paged_slot_verify": ((model, pcache, pos, active, tokens,
                               q_lens), {}),
        "slot_mixed": ((model, logits0, cache, pos, active, prefilling,
                        tokens, q_lens, keys), {}),
        "paged_slot_mixed": ((model, logits0, pcache, pos, active,
                              prefilling, tokens, q_lens, keys), {}),
        "gather_pages": ((model, pcache, ids), {}),
        "restore_pages": ((model, pcache, ids, hk, hv), {}),
    }
    return {name: (body[name], args, kwargs)
            for name, (args, kwargs) in calls.items()}


def check_engine(engine, batch: int = 2,
                 report: Optional[Report] = None) -> Report:
    if report is None:
        report = Report("hotloop")
    for name, (fn, args, kwargs) in canonical_programs(
            engine, batch).items():
        subject = f"{name}[{engine.backend}]"
        try:
            check_host_transfers(fn, args, kwargs, subject, report)
            check_trace_determinism(fn, args, kwargs, subject, report)
            report.covered.append(subject)
        except Exception as e:
            report.add("error",
                       "triton_dist_tpu/models/engine.py", subject,
                       f"decode-tick program failed to trace at "
                       f"canonical shapes: {e!r}")
    return report


def run(report: Optional[Report] = None) -> Report:
    """CLI entry: the canonical tiny engine's full decode-tick program
    surface + the process-wide program-cache identity check."""
    import jax
    from triton_dist_tpu.models import AutoLLM, Engine
    from triton_dist_tpu.models.config import tiny_qwen3
    if report is None:
        report = Report("hotloop")
    check_program_cache_identity(report)
    mesh = jax.make_mesh((1,), ("tp",), devices=jax.devices()[:1])
    model = AutoLLM.from_config(tiny_qwen3(1), mesh)
    engine = Engine(model, max_seq=64, backend="flash")
    check_engine(engine, report=report)
    return report
