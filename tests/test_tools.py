"""Autotuner + perf-model tests (reference analogs:
python/triton_dist/tools/tune.py's cache/consensus behavior and the
gemm_perf_model sanity checks)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from triton_dist_tpu.tools import (AutoTuner, autotune, chip_specs,
                                   clear_cache, collective_sol_us,
                                   gemm_sol_us, sol_report)


@pytest.fixture()
def cache_path(tmp_path):
    return str(tmp_path / "autotune.json")


def test_autotuner_picks_fastest_and_caches(cache_path):
    calls = {"n": 0}

    def op(x, *, block):
        calls["n"] += 1
        # block=2 artificially slow: burn host time the timer sees
        if block == 2:
            import time
            time.sleep(0.01)
        return x * block

    tuner = AutoTuner(op, [{"block": 2}, {"block": 3}],
                      cache_path=cache_path, iters=1, warmup=0)
    x = jnp.ones((4, 4))
    cfg = tuner.pick(x)
    assert cfg == {"block": 3}
    n_after_tune = calls["n"]
    # cached: replay without re-measuring
    out = tuner(x)
    assert calls["n"] == n_after_tune + 1
    np.testing.assert_array_equal(np.asarray(out), 3 * np.ones((4, 4)))
    # on-disk cache has the entry
    with open(cache_path) as f:
        disk = json.load(f)
    (entry,) = disk.values()
    assert entry["cfg"] == {"block": 3}


def test_autotuner_cache_survives_new_instance(cache_path):
    def op(x, *, block):
        return x + block

    t1 = AutoTuner(op, [{"block": 1}, {"block": 2}],
                   cache_path=cache_path, iters=1, warmup=0)
    cfg1 = t1.pick(jnp.ones((2, 2)))
    measured = {"n": 0}

    def op2(x, *, block):
        measured["n"] += 1
        return x + block

    t2 = AutoTuner(op2, [{"block": 1}, {"block": 2}], name=op.__name__,
                   cache_path=cache_path, iters=1, warmup=0)
    cfg2 = t2.pick(jnp.ones((2, 2)))
    assert cfg2 == cfg1 and measured["n"] == 0   # pure cache hit


def test_autotuner_distinct_signatures(cache_path):
    def op(x, *, block):
        return x * block

    t = AutoTuner(op, [{"block": 1}, {"block": 4}],
                  cache_path=cache_path, iters=1, warmup=0)
    t.pick(jnp.ones((2, 2)))
    t.pick(jnp.ones((8, 8)))
    with open(cache_path) as f:
        assert len(json.load(f)) == 2


def test_autotune_decorator_skips_failing_config(cache_path):
    @autotune([{"block": 7}, {"block": 8}], cache_path=cache_path,
              iters=1, warmup=0)
    def op(x, *, block):
        if block == 7:
            raise ValueError("illegal tile")
        return x * block

    out = op(jnp.ones((2, 2)))
    np.testing.assert_array_equal(np.asarray(out), 8 * np.ones((2, 2)))


def test_clear_cache(cache_path):
    def op(x, *, b):
        return x

    AutoTuner(op, [{"b": 1}], cache_path=cache_path, iters=1,
              warmup=0).pick(jnp.ones(2))
    assert os.path.exists(cache_path)
    clear_cache(cache_path)
    assert not os.path.exists(cache_path)


def test_perf_models_sanity():
    spec = chip_specs("TPU v5e")
    assert spec.name == "v5e"
    # square bf16 GEMM large enough to be FLOPs-bound
    t = gemm_sol_us(4096, 4096, 4096, spec=spec)
    flops = 2 * 4096 ** 3
    assert abs(t - flops / (spec.bf16_tflops * 1e12) * 1e6) / t < 1e-6
    # tiny GEMM is bandwidth-bound
    t2 = gemm_sol_us(8, 4096, 4096, spec=spec)
    assert t2 > 2 * 8 * 4096 * 4096 / (spec.bf16_tflops * 1e12) * 1e6
    # AR moves 2(n-1)/n, AG (n-1)/n: ratio 2
    ag = collective_sol_us("ag", 1 << 20, 8, spec=spec)
    ar = collective_sol_us("ar", 1 << 20, 8, spec=spec)
    assert abs(ar / ag - 2.0) < 1e-9
    assert collective_sol_us("ag", 1 << 20, 1, spec=spec) == 0.0
    line = sol_report("ag_gemm", 100.0, 80.0)
    assert "80.0" in line and "%" in line


def test_trace_view_cli(tmp_path):
    """tools/trace_view.py (repo-root CLI, stdlib-only): summarizes a
    TDTPU_TRACE dump — per-phase time shares, top-k slowest polls, the
    per-request TTFT table and the embedded histogram snapshot."""
    import subprocess
    import sys

    dump = {
        "traceEvents": [
            {"ph": "M", "pid": 0, "tid": 0, "name": "thread_name",
             "args": {"name": "host phases"}},
            {"name": "poll", "ph": "X", "pid": 0, "tid": 0, "ts": 0,
             "dur": 1000, "args": {"seq": 1}},
            {"name": "poll", "ph": "X", "pid": 0, "tid": 0, "ts": 1500,
             "dur": 3000, "args": {"seq": 2}},
            {"name": "bookkeep", "ph": "X", "pid": 0, "tid": 0,
             "ts": 10, "dur": 200},
            {"name": "dispatch", "ph": "X", "pid": 0, "tid": 0,
             "ts": 300, "dur": 500},
            {"name": "device:chunk", "ph": "X", "pid": 0, "tid": 1,
             "ts": 320, "dur": 2400},
            {"name": "preempt", "ph": "i", "s": "p", "pid": 0,
             "tid": 0, "ts": 900},
        ],
        "requests": {
            "0": {"status": "retired", "tokens": 12, "ttft_ms": 4.2,
                  "events": [[0.0, "queued", None]]},
            "1": {"status": "cancelled", "tokens": 3, "ttft_ms": None,
                  "events": [[0.1, "queued", None]]},
        },
        "metrics": {"ttft_ms": {"count": 2, "sum": 8.4, "mean": 4.2,
                                "p50": 4.2, "p95": 4.3, "p99": 4.3}},
    }
    path = tmp_path / "trace.json"
    path.write_text(json.dumps(dump))
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "trace_view.py"),
         str(path), "--top", "1"],
        capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    text = out.stdout
    assert "polls: 2" in text
    assert "bookkeep" in text and "dispatch" in text
    assert "device occupancy" in text
    assert "poll #2" in text and "poll #1" not in text   # --top 1
    assert "preempt=1" in text
    assert "retired" in text and "cancelled" in text
    assert "ttft_ms: n=2" in text


def test_trace_view_programs_table(tmp_path):
    """The programs table: per role the compile accounting's totals
    from the dump's metrics (programs compiled, cache hits, trace /
    lower / backend seconds) and the `compile:<role>` spans that lie
    inside a poll span, in the text report and in --json."""
    import subprocess
    import sys

    def span(name, ts, dur, **args):
        return {"name": name, "ph": "X", "pid": 0, "tid": 0, "ts": ts,
                "dur": dur, "args": args}

    key = "program_compile_{}{{program={},stage={}}}".format
    dump = {
        "traceEvents": [
            span("poll", 0, 1000, seq=1),
            span("admit", 50, 800),
            span("compile:paged_admit", 100, 300, stage="trace",
                 seconds=0.0003),
            span("compile:paged_admit", 400, 400, stage="backend",
                 seconds=0.0004),
            # between two polls: the warm-up's, not the loop's
            span("compile:eager", 1200, 100, stage="backend",
                 seconds=0.0001),
            span("poll", 1500, 500, seq=2),
        ],
        "requests": {},
        "metrics": {
            key("s", "paged_admit", "trace"): 12.5,
            key("s", "paged_admit", "lower"): 3.25,
            key("s", "paged_admit", "backend"): 20.0,
            key("s", "paged_admit", "cache_load"): 1.5,
            key("n", "paged_admit", "trace"): 3,
            key("n", "paged_admit", "backend"): 3,
            key("n", "paged_admit", "cache_load"): 2,
            key("s", "eager", "backend"): 0.75,
            key("n", "eager", "backend"): 40,
            "host_phase_s{phase=admit}": 1.0,
        },
    }
    path = tmp_path / "trace.json"
    path.write_text(json.dumps(dump))
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    tool = os.path.join(repo, "tools", "trace_view.py")
    out = subprocess.run([sys.executable, tool, str(path), "--json"],
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    programs = json.loads(out.stdout)["programs"]
    assert programs == {
        "paged_admit": {"compiled": 3, "cache_hits": 2, "trace_s": 12.5,
                        "lower_s": 3.25, "backend_s": 20.0,
                        "in_poll_n": 2, "in_poll_ms": 0.7},
        "eager": {"compiled": 40, "cache_hits": 0, "trace_s": 0.0,
                  "lower_s": 0.0, "backend_s": 0.75, "in_poll_n": 0,
                  "in_poll_ms": 0.0}}
    out = subprocess.run([sys.executable, tool, str(path)],
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    rows = [ln.split() for ln in out.stdout.splitlines()
            if ln.startswith("  paged_admit") or ln.startswith("  eager")]
    # most compile seconds first; compiled / hits, then the seconds
    assert [r[0] for r in rows] == ["paged_admit", "eager"]
    assert rows[0][1:7] == ["3", "/", "2", "12.500", "3.250", "20.000"]
    assert rows[0][-2:] == ["2", "(0.700ms)"]


def test_kernel_context_tune_cold_and_warm(cache_path, monkeypatch):
    """The wired path (VERDICT r2 #7): create_ag_gemm_context(tune=True)
    cold-tunes over the block space and caches; a second creation with
    the same signature replays the cached winner without re-timing."""
    import json
    import os
    monkeypatch.setenv("TDTPU_AUTOTUNE_CACHE", cache_path)
    import jax
    from triton_dist_tpu.kernels import ag_gemm, create_ag_gemm_context
    n = len(jax.devices())
    mesh = jax.make_mesh((n,), ("tp",))
    K, N_loc = 128, 128
    ctx = create_ag_gemm_context(mesh, K=K, N_local=N_loc,
                                 dtype=jnp.float32, tune=True, tune_M=8 * n)
    assert ctx.block_n in (256, 512, 1024, 2048)
    cache = json.load(open(cache_path))
    assert any("ag_gemm" in k for k in cache)      # cold run cached
    mtime = os.path.getmtime(cache_path)
    ctx2 = create_ag_gemm_context(mesh, K=K, N_local=N_loc,
                                  dtype=jnp.float32, tune=True,
                                  tune_M=8 * n)
    assert ctx2.block_n == ctx.block_n             # warm run hits
    assert os.path.getmtime(cache_path) == mtime   # ...without rewriting
    # and the tuned context actually computes correctly
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    rng = np.random.RandomState(0)
    a = jnp.asarray(rng.randn(8 * n, K), jnp.float32)
    b = jnp.asarray(rng.randn(K, N_loc * n), jnp.float32)
    a_s = jax.device_put(a, NamedSharding(mesh, P("tp", None)))
    b_s = jax.device_put(b, NamedSharding(mesh, P(None, "tp")))
    with jax.default_matmul_precision("highest"):
        y = jax.jit(lambda x, w: ag_gemm(x, w, ctx))(a_s, b_s)
    np.testing.assert_allclose(np.asarray(y), np.asarray(a @ b),
                               atol=1e-4, rtol=1e-4)


def test_contextual_autotune_profiles_nested_kernels(cache_path,
                                                     monkeypatch):
    """contextual_autotune (reference autotuner.py:97): tunes a nested
    kernel inside a composite forward; the winner is installed in the
    profile the kernel default consults, cached, and replayed."""
    monkeypatch.setenv("TDTPU_AUTOTUNE_CACHE", cache_path)
    import jax
    import numpy as np
    from triton_dist_tpu.kernels import flash_decode
    from triton_dist_tpu.tools.tune import (contextual_autotune,
                                            contextual_choice,
                                            set_contextual)
    set_contextual({})
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(2, 1, 4, 128), jnp.float32)
    k = jnp.asarray(rng.randn(2, 2, 64, 128), jnp.float32)
    v = jnp.asarray(rng.randn(2, 2, 64, 128), jnp.float32)

    def composite(q, k, v):
        o = flash_decode(q, k, v, jnp.int32(64))
        return jnp.sum(o.astype(jnp.float32))

    vary = {"flash_decode": [{"block_t": 32}, {"block_t": 64}]}
    prof = contextual_autotune(composite, (q, k, v), vary,
                               name="test_layer")
    assert prof["flash_decode"]["block_t"] in (32, 64)
    assert contextual_choice("flash_decode") == prof["flash_decode"]
    # warm: the cached profile is returned without re-timing
    set_contextual({})
    prof2 = contextual_autotune(composite, (q, k, v), vary,
                                name="test_layer")
    assert prof2 == prof
    assert contextual_choice("flash_decode") == prof["flash_decode"]
    set_contextual({})
