"""Fleet-grade observability (ISSUE 11): SLO-class goodput accounting
through the scheduler, per-program-kind device-time attribution, the
perf-regression ledger (bench.py BENCH_history.jsonl +
tools/bench_compare.py), and the merged cross-plane trace from a
threaded disaggregated TokenServer.

The cheap arms run in tier-1 (the engine-based tests reuse the same
tiny-model/program shapes as tests/test_telemetry.py, so they add no
compile bill); the threaded TokenServer merged-trace run and the
disagg trace-on==off bitwise arm carry `slow` — tools/obs_smoke.sh is
the focused full-matrix loop. The inline cross-plane flow contract is
pinned tier-1 by tests/test_disagg.py's churn-guard run (trace=ON).
"""

import importlib.util
import json
import os
import threading

import jax
import numpy as np
import pytest

from triton_dist_tpu.models import (AutoLLM, ContinuousScheduler, Engine,
                                    Request)
from triton_dist_tpu.models.config import tiny_qwen3
from triton_dist_tpu.runtime.telemetry import prometheus_text

mesh = None
_ENGINES = {}

_REPO = os.path.join(os.path.dirname(__file__), "..")


def setup_module(module):
    global mesh
    n = len(jax.devices())
    mesh = jax.make_mesh((n,), ("tp",))


def _engine(mode="greedy"):
    """Same config as tests/test_telemetry.py's engine so the slot
    programs are shared process-wide (engine._jit_programs) — this
    module adds ~zero compile bill to tier-1."""
    if mode not in _ENGINES:
        cfg = tiny_qwen3(mesh.shape["tp"])
        model = AutoLLM.from_config(cfg, mesh)
        _ENGINES[mode] = (cfg, Engine(model, max_seq=64,
                                      backend="xla"))
    return _ENGINES[mode]


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ----------------------------------------------------------------------
# SLO classes + goodput through the scheduler (acceptance: a mixed
# interactive+batch burst partitions the counters exactly)
# ----------------------------------------------------------------------

def test_slo_burst_partition_and_attribution():
    """One mixed burst: interactive requests (infinite targets -> all
    goodput), batch requests (impossible TTFT target -> all
    violations), one untagged (outside the partition). Asserts the
    per-class counters partition exactly, the per-class histograms got
    exactly the tagged samples, the Prometheus exposition carries the
    labeled series — and the same run's device-wait attribution: the
    coalesced device_wait_s splits per program kind with the decode
    bucket dominant."""
    cfg, eng = _engine()
    sched = ContinuousScheduler(
        eng, batch=3, chunk=4, paged=True, page=8,
        slo_classes={
            "interactive": {"ttft_target_ms": 1e9,
                            "itl_target_ms": 1e9},
            "batch": {"ttft_target_ms": 0.0, "itl_target_ms": 0.0},
        })
    rng = np.random.RandomState(0)
    spec = [(5, 6, "interactive"), (20, 8, "batch"), (3, 4, None),
            (12, 10, "interactive"), (7, 9, "batch")]
    reqs = []
    for i, (L, g, slo) in enumerate(spec):
        ids = rng.randint(0, cfg.vocab_size, size=(L,)).astype(np.int32)
        reqs.append(Request(rid=i, ids=ids, gen_len=g, seed=100 + i,
                            slo=slo))
    out = sched.run(reqs)
    assert len(out) == len(reqs)

    st = sched.stats()
    # exact partition per class: goodput + violations == submitted
    assert st["slo_goodput{slo=interactive}"] == 2
    assert st["slo_violations{slo=interactive}"] == 0
    assert st["slo_goodput{slo=batch}"] == 0
    assert st["slo_violations{slo=batch}"] == 2
    # per-class TTFT histograms got exactly the tagged samples; the
    # aggregate histogram has everyone (untagged included)
    assert st["ttft_ms{slo=interactive}"]["count"] == 2
    assert st["ttft_ms{slo=batch}"]["count"] == 2
    assert st["ttft_ms"]["count"] == 5
    assert st["inter_token_ms{slo=interactive}"]["count"] > 0
    # config echo for operators
    assert st["slo_classes"]["batch"]["ttft_target_ms"] == 0.0
    json.dumps(st)

    # the Prometheus exposition carries the labeled series
    text = prometheus_text(sched.tele.registry)
    assert 'tdtpu_slo_goodput{slo="interactive"} 2' in text
    assert 'tdtpu_slo_violations{slo="batch"} 2' in text
    assert 'tdtpu_ttft_ms_bucket{le="+Inf",slo="interactive"} 2' \
        in text
    assert text.count("# TYPE tdtpu_ttft_ms histogram") == 1

    # device-time attribution: the fused buckets sum to the coalesced
    # device_wait_s (prefill/transfer are the disagg plane's buckets)
    by_kind = st["device_wait_s_by_kind"]
    assert by_kind.get("decode", 0.0) > 0.0
    fused = sum(v for k, v in by_kind.items()
                if k in ("decode", "verify", "mixed", "admit",
                         "other"))
    assert abs(fused - st["device_wait_s"]) < 0.01
    assert st["device_wait_kind_s{kind=decode}"] == by_kind["decode"]


def test_slo_untagged_requests_unaffected():
    """A scheduler with default classes and NO tagged requests keeps
    its counters at zero — tagging is opt-in, never inferred."""
    cfg, eng = _engine()
    sched = ContinuousScheduler(eng, batch=3, chunk=4)
    rng = np.random.RandomState(1)
    reqs = [Request(rid=i, ids=rng.randint(
                0, cfg.vocab_size, size=(5,)).astype(np.int32),
                gen_len=4, seed=i) for i in range(2)]
    sched.run(reqs)
    st = sched.stats()
    assert st["slo_goodput{slo=interactive}"] == 0
    assert st["slo_violations{slo=interactive}"] == 0
    assert st["slo_goodput{slo=batch}"] == 0
    assert sorted(st["slo_classes"]) == ["batch", "interactive"]


# ----------------------------------------------------------------------
# perf-regression ledger: bench.py history + tools/bench_compare.py
# ----------------------------------------------------------------------

def test_trace_view_plane_union_and_phase_filter():
    """Plane time is the interval UNION per track (nested host phase
    spans must not double-count against the worker planes), and the
    phase table covers only the scheduler's named phases (a kv_install
    span stamped inside bookkeep is not a second 'phase')."""
    tv = _load_tool("trace_view")
    dump = {"traceEvents": [
        {"ph": "M", "pid": 0, "tid": 2, "name": "thread_name",
         "args": {"name": "prefill-worker-0"}},
        # one 100ms poll containing a 40ms bookkeep, which contains a
        # 10ms kv_install; a disjoint 30ms worker span
        {"ph": "X", "pid": 0, "tid": 0, "name": "poll",
         "ts": 0.0, "dur": 100e3, "args": {"seq": 1}},
        {"ph": "X", "pid": 0, "tid": 0, "name": "bookkeep",
         "ts": 10e3, "dur": 40e3},
        {"ph": "X", "pid": 0, "tid": 0, "name": "kv_install",
         "ts": 20e3, "dur": 10e3},
        {"ph": "X", "pid": 0, "tid": 2, "name": "prefill:compute",
         "ts": 120e3, "dur": 30e3},
    ]}
    a = tv.analyze(dump)
    assert a["planes"]["host phases"]["ms"] == 100.0   # union, not 150
    assert a["planes"]["prefill-worker-0"]["ms"] == 30.0
    assert abs(a["planes"]["host phases"]["share"]
               - 100.0 / 130.0) < 1e-3
    assert "kv_install" not in a["phases"]
    assert a["phases"]["bookkeep"]["ms"] == 40.0
    assert a["phases"]["bookkeep"]["share"] == 0.4


def test_bench_history_append(tmp_path, monkeypatch):
    """Every _emit_json capture appends one enriched line (run id, git
    sha, host, timestamp) to the ledger; TDTPU_BENCH_HISTORY='' turns
    it off."""
    path = tmp_path / "hist.jsonl"
    monkeypatch.setenv("TDTPU_BENCH_HISTORY", str(path))
    monkeypatch.delenv("TDTPU_BENCH_JSON", raising=False)
    spec = importlib.util.spec_from_file_location(
        "bench_mod", os.path.join(_REPO, "bench.py"))
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    bench._emit_json({"metric": "m1", "value": 1.5, "unit": "ms",
                      "backend": "cpu"})
    bench._emit_json({"metric": "m2", "value": 2.0, "unit": "tok/s",
                      "backend": "cpu"})
    rows = [json.loads(ln) for ln in path.read_text().splitlines()]
    assert [r["metric"] for r in rows] == ["m1", "m2"]
    for r in rows:
        assert r["run"] and r["git_sha"] and r["host"]
        assert isinstance(r["unix"], float)
    assert rows[0]["run"] == rows[1]["run"]     # one capture, one run
    monkeypatch.setenv("TDTPU_BENCH_HISTORY", "")
    bench._emit_json({"metric": "m3", "value": 3.0, "unit": "ms"})
    assert len(path.read_text().splitlines()) == 2


def test_bench_compare_flags_and_gating(tmp_path):
    """Direction inference (ms regress UP, tok/s regress DOWN), the
    noise threshold, the advisory notes (cpu-smoke / cross-backend /
    zero-baseline) that keep smoke noise from hard-failing, and the
    --strict gate that only trusts same-backend non-cpu rows."""
    bc = _load_tool("bench_compare")
    a = [{"metric": "lat_ms", "value": 10.0, "unit": "ms",
          "backend": "tpu"},
         {"metric": "tps", "value": 100.0, "unit": "tok/s",
          "backend": "tpu"},
         {"metric": "steady", "value": 50.0, "unit": "tok/s",
          "backend": "tpu"},
         {"metric": "smoke", "value": 10.0, "unit": "ms",
          "backend": "cpu"},
         {"metric": "mixed", "value": 5.0, "unit": "ms",
          "backend": "tpu"},
         {"metric": "outage", "value": 0.0, "unit": "tok/s",
          "backend": "tpu"}]
    b = [{"metric": "lat_ms", "value": 20.0, "unit": "ms",
          "backend": "tpu"},              # 2x slower -> regressed
         {"metric": "tps", "value": 140.0, "unit": "tok/s",
          "backend": "tpu"},              # faster -> improved
         {"metric": "steady", "value": 55.0, "unit": "tok/s",
          "backend": "tpu"},              # +10% -> noise
         {"metric": "smoke", "value": 40.0, "unit": "ms",
          "backend": "cpu"},              # regressed but cpu-smoke
         {"metric": "mixed", "value": 50.0, "unit": "ms",
          "backend": "cpu"},              # cross-backend, advisory
         {"metric": "outage", "value": 7.0, "unit": "tok/s",
          "backend": "tpu"}]              # zero baseline: no ratio
    res = {r["metric"]: r for r in bc.compare(a, b)}
    assert res["lat_ms"]["flag"] == "regressed" \
        and not res["lat_ms"]["notes"]
    assert res["lat_ms"]["delta_pct"] == 100.0
    assert res["tps"]["flag"] == "improved"
    assert res["steady"]["flag"] == "noise"
    assert res["smoke"]["flag"] == "regressed" \
        and "cpu-smoke" in res["smoke"]["notes"]
    assert "cross-backend" in res["mixed"]["notes"]
    assert res["outage"]["flag"] == "noise" \
        and "zero-baseline" in res["outage"]["notes"]
    gating = bc.gating_regressions(list(res.values()))
    assert [g["metric"] for g in gating] == ["lat_ms"]

    # the CLI: file mode, --strict rc, --json output
    fa, fb = tmp_path / "a.json", tmp_path / "b.json"
    fa.write_text("".join(json.dumps(r) + "\n" for r in a))
    fb.write_text("".join(json.dumps(r) + "\n" for r in b))
    assert bc.main([str(fa), str(fb)]) == 0       # never hard-fails
    assert bc.main([str(fa), str(fb), "--strict"]) == 1
    # drop the gating row: strict passes on smoke noise alone
    fb2 = tmp_path / "b2.json"
    fb2.write_text("".join(json.dumps(r) + "\n" for r in b
                           if r["metric"] != "lat_ms"))
    assert bc.main([str(fa), str(fb2), "--strict"]) == 0


def test_bench_compare_seconds_unit_is_latency_direction():
    """ISSUE 12 satellite bugfix: plain-seconds rows — the new
    `aot_warm_start_s` — are latency-direction (s UP = regressed),
    both through the unit token ("s", annotated spellings) and the
    metric-name `_s` suffix convention; throughput rows whose names
    merely contain "_s_" (tok_per_s_aggregate) keep their
    higher-is-better direction."""
    bc = _load_tool("bench_compare")
    a = [{"metric": "aot_warm_start_s", "value": 2.0, "unit": "s",
          "backend": "tpu"},
         {"metric": "aot_warm_start_s2", "value": 2.0,
          "unit": "s (restart)", "backend": "tpu"},
         {"metric": "serving_tok_per_s_aggregate", "value": 100.0,
          "unit": "tok/s", "backend": "tpu"}]
    b = [{"metric": "aot_warm_start_s", "value": 6.0, "unit": "s",
          "backend": "tpu"},              # 3x slower restart
         {"metric": "aot_warm_start_s2", "value": 6.0,
          "unit": "s (restart)", "backend": "tpu"},
         {"metric": "serving_tok_per_s_aggregate", "value": 200.0,
          "unit": "tok/s", "backend": "tpu"}]
    res = {r["metric"]: r for r in bc.compare(a, b)}
    assert res["aot_warm_start_s"]["flag"] == "regressed"
    assert res["aot_warm_start_s"]["direction"] == "lower-is-better"
    assert res["aot_warm_start_s2"]["flag"] == "regressed"
    assert res["serving_tok_per_s_aggregate"]["flag"] == "improved"


def test_bench_compare_moe_row_directions():
    """ISSUE 13 satellite: the two new MoE bench rows resolve to the
    right regression direction — `moe_serving_tok_per_s_per_chip`
    (tok/s, a rate: DOWN = regressed) and `moe_grouped_gemm_speedup`
    (unit "x", a speedup multiplier: DOWN = regressed, despite no
    "/" in the unit)."""
    bc = _load_tool("bench_compare")
    a = [{"metric": "moe_serving_tok_per_s_per_chip", "value": 100.0,
          "unit": "tok/s", "backend": "tpu"},
         {"metric": "moe_grouped_gemm_speedup", "value": 3.0,
          "unit": "x", "backend": "tpu"}]
    b = [{"metric": "moe_serving_tok_per_s_per_chip", "value": 50.0,
          "unit": "tok/s", "backend": "tpu"},
         {"metric": "moe_grouped_gemm_speedup", "value": 1.2,
          "unit": "x", "backend": "tpu"}]
    res = {r["metric"]: r for r in bc.compare(a, b)}
    assert res["moe_serving_tok_per_s_per_chip"]["flag"] == "regressed"
    assert res["moe_serving_tok_per_s_per_chip"]["direction"] \
        == "higher-is-better"
    assert res["moe_grouped_gemm_speedup"]["flag"] == "regressed"
    assert res["moe_grouped_gemm_speedup"]["direction"] \
        == "higher-is-better"


def test_bench_compare_sp_row_directions():
    """ISSUE 14 satellite: the two sequence-parallel bench rows
    resolve to the right regression direction —
    `sp_decode_tok_per_s_per_chip` (tok/s, a rate: DOWN = regressed)
    and `long_context_capacity_multiplier` (unit "x", a capacity
    multiplier: DOWN = regressed)."""
    bc = _load_tool("bench_compare")
    a = [{"metric": "sp_decode_tok_per_s_per_chip", "value": 200.0,
          "unit": "tok/s", "backend": "tpu"},
         {"metric": "long_context_capacity_multiplier", "value": 4.0,
          "unit": "x", "backend": "tpu"}]
    b = [{"metric": "sp_decode_tok_per_s_per_chip", "value": 90.0,
          "unit": "tok/s", "backend": "tpu"},
         {"metric": "long_context_capacity_multiplier", "value": 1.0,
          "unit": "x", "backend": "tpu"}]
    res = {r["metric"]: r for r in bc.compare(a, b)}
    assert res["sp_decode_tok_per_s_per_chip"]["flag"] == "regressed"
    assert res["sp_decode_tok_per_s_per_chip"]["direction"] \
        == "higher-is-better"
    assert res["long_context_capacity_multiplier"]["flag"] == "regressed"
    assert res["long_context_capacity_multiplier"]["direction"] \
        == "higher-is-better"


def test_bench_compare_structured_row_directions():
    """ISSUE 17 satellite: the two structured-generation bench rows
    resolve to the right regression direction —
    `parallel_sampling_prefill_skip_frac` (unit "frac": a shared-work
    fraction, DOWN = regressed) and `constrained_decode_tok_per_s`
    (tok/s: DOWN = regressed — the metric NAME ends in "_s", so only
    the rate-unit "/" rule keeps it from resolving as a latency)."""
    bc = _load_tool("bench_compare")
    a = [{"metric": "parallel_sampling_prefill_skip_frac",
          "value": 0.75, "unit": "frac", "backend": "tpu"},
         {"metric": "constrained_decode_tok_per_s", "value": 700.0,
          "unit": "tok/s", "backend": "tpu"}]
    b = [{"metric": "parallel_sampling_prefill_skip_frac",
          "value": 0.25, "unit": "frac", "backend": "tpu"},
         {"metric": "constrained_decode_tok_per_s", "value": 300.0,
          "unit": "tok/s", "backend": "tpu"}]
    res = {r["metric"]: r for r in bc.compare(a, b)}
    assert res["parallel_sampling_prefill_skip_frac"]["flag"] \
        == "regressed"
    assert res["parallel_sampling_prefill_skip_frac"]["direction"] \
        == "higher-is-better"
    assert res["constrained_decode_tok_per_s"]["flag"] == "regressed"
    assert res["constrained_decode_tok_per_s"]["direction"] \
        == "higher-is-better"


def test_bench_compare_fleet_row_directions():
    """ISSUE 18 satellite: the two fleet traffic-plane bench rows
    resolve to the right regression direction —
    `router_storm_p99_ttft_ms` (unit "ms", a latency: UP = regressed)
    and `fleet_prefix_hit_frac` (unit "frac", a placement hit rate:
    DOWN = regressed)."""
    bc = _load_tool("bench_compare")
    a = [{"metric": "router_storm_p99_ttft_ms", "value": 40.0,
          "unit": "ms", "backend": "tpu"},
         {"metric": "fleet_prefix_hit_frac", "value": 0.75,
          "unit": "frac", "backend": "tpu"}]
    b = [{"metric": "router_storm_p99_ttft_ms", "value": 160.0,
          "unit": "ms", "backend": "tpu"},
         {"metric": "fleet_prefix_hit_frac", "value": 0.25,
          "unit": "frac", "backend": "tpu"}]
    res = {r["metric"]: r for r in bc.compare(a, b)}
    assert res["router_storm_p99_ttft_ms"]["flag"] == "regressed"
    assert res["router_storm_p99_ttft_ms"]["direction"] \
        == "lower-is-better"
    assert res["fleet_prefix_hit_frac"]["flag"] == "regressed"
    assert res["fleet_prefix_hit_frac"]["direction"] \
        == "higher-is-better"


def test_bench_compare_ha_row_directions():
    """ISSUE 19 satellite: the two fleet HA bench rows resolve to the
    right regression direction — `failover_recovery_ms` (unit "ms",
    the standby-promotion latency: UP = regressed) and
    `dedup_hit_rate` (unit "frac", the exactly-once window's retry
    absorption: DOWN = regressed)."""
    bc = _load_tool("bench_compare")
    a = [{"metric": "failover_recovery_ms", "value": 12.0,
          "unit": "ms", "backend": "tpu"},
         {"metric": "dedup_hit_rate", "value": 1.0,
          "unit": "frac", "backend": "tpu"}]
    b = [{"metric": "failover_recovery_ms", "value": 48.0,
          "unit": "ms", "backend": "tpu"},
         {"metric": "dedup_hit_rate", "value": 0.25,
          "unit": "frac", "backend": "tpu"}]
    res = {r["metric"]: r for r in bc.compare(a, b)}
    assert res["failover_recovery_ms"]["flag"] == "regressed"
    assert res["failover_recovery_ms"]["direction"] \
        == "lower-is-better"
    assert res["dedup_hit_rate"]["flag"] == "regressed"
    assert res["dedup_hit_rate"]["direction"] == "higher-is-better"


def test_bench_compare_history_mode(tmp_path):
    """--history groups the ledger by run id and diffs the last two
    runs."""
    bc = _load_tool("bench_compare")
    hist = tmp_path / "BENCH_history.jsonl"
    rows = [
        {"metric": "tps", "value": 100.0, "unit": "tok/s",
         "backend": "tpu", "run": "r1"},
        {"metric": "tps", "value": 120.0, "unit": "tok/s",
         "backend": "tpu", "run": "r2"},
        {"metric": "tps", "value": 40.0, "unit": "tok/s",
         "backend": "tpu", "run": "r3"},
    ]
    hist.write_text("".join(json.dumps(r) + "\n" for r in rows))
    runs = bc.history_runs(str(hist))
    assert [r[0] for r in runs] == ["r1", "r2", "r3"]
    assert bc.main(["--history", "--file", str(hist)]) == 0
    # the last pair (r2 -> r3) is a -66% regression: strict trips
    assert bc.main(["--history", "--file", str(hist),
                    "--strict"]) == 1
    assert bc.main(["--history", "--file",
                    str(tmp_path / "missing.jsonl")]) == 2


def test_bench_compare_sol_frac_direction():
    """ISSUE 16: roofline rows (`{op}_sol_frac`, unit "frac of SOL"
    from perf_report.sol_frac_rows) are higher-is-better — an
    achieved/SOL fraction going DOWN is the regression — and the rule
    must fire on the metric suffix alone even when the unit string is
    missing (hand-rolled captures)."""
    bc = _load_tool("bench_compare")
    assert not bc._lower_is_better({"metric": "ag_gemm_sol_frac",
                                    "value": 0.7, "unit": "frac of SOL"})
    assert not bc._lower_is_better({"metric": "flash_decode_sol_frac",
                                    "value": 0.7})         # no unit
    # a latency-suffixed op name still resolves higher-is-better
    # through the sol_frac suffix (the suffix rule runs FIRST)
    assert not bc._lower_is_better(
        {"metric": "warm_start_s_sol_frac", "unit": "frac of SOL"})
    # and plain latency rows are untouched by the new rule
    assert bc._lower_is_better({"metric": "lat_ms", "unit": "ms"})
    a = [{"metric": "gemm_rs_sol_frac", "value": 0.80,
          "unit": "frac of SOL", "backend": "tpu"}]
    b = [{"metric": "gemm_rs_sol_frac", "value": 0.40,
          "unit": "frac of SOL", "backend": "tpu"}]
    res = bc.compare(a, b)[0]
    assert res["direction"] == "higher-is-better"
    assert res["flag"] == "regressed" and not res["notes"]


def test_bench_compare_strict_gates_roofline_regression(tmp_path):
    """The closed perf loop's exit check: a seeded same-backend
    roofline regression in the history tail trips --strict (exit 1); a
    clean tail — and a cpu-smoke one — exits 0."""
    bc = _load_tool("bench_compare")
    hist = tmp_path / "hist.jsonl"
    rows = [
        {"metric": "flash_decode_sol_frac", "value": 0.60,
         "unit": "frac of SOL", "backend": "tpu", "run": "r1"},
        {"metric": "flash_decode_sol_frac", "value": 0.20,
         "unit": "frac of SOL", "backend": "tpu", "run": "r2"},
    ]
    hist.write_text("".join(json.dumps(r) + "\n" for r in rows))
    assert bc.main(["--history", "--file", str(hist), "--strict"]) == 1
    # clean tail: fraction recovered -> improvement, strict passes
    rows.append({"metric": "flash_decode_sol_frac", "value": 0.65,
                 "unit": "frac of SOL", "backend": "tpu", "run": "r3"})
    hist.write_text("".join(json.dumps(r) + "\n" for r in rows))
    assert bc.main(["--history", "--file", str(hist), "--strict"]) == 0
    # the same regression on the cpu smoke substrate stays advisory
    cpu = tmp_path / "cpu.jsonl"
    cpu.write_text("".join(json.dumps(dict(r, backend="cpu")) + "\n"
                           for r in rows[:2]))
    assert bc.main(["--history", "--file", str(cpu), "--strict"]) == 0


def test_sol_frac_rows_shape():
    """perf_report.sol_frac_rows flattens a report dict into ledger
    rows: one {op}_sol_frac per measured op, degenerate rows (elided
    chain / failed op: sol_frac None) dropped, env backend stamped."""
    from triton_dist_tpu.tools.perf_report import (GATE_OPS,
                                                   sol_frac_rows)
    rep = {"env": {"backend": "tpu", "ndev": 8, "interpreted": False},
           "ops": [{"op": "ag_gemm", "achieved_us": 20.0, "sol_us": 10.0,
                    "sol_frac": 0.5, "note": ""},
                   {"op": "pp_gpipe_fwd", "achieved_us": None,
                    "sol_us": 5.0, "sol_frac": None,
                    "note": "DEGENERATE"}]}
    rows = sol_frac_rows(rep)
    assert [r["metric"] for r in rows] == ["ag_gemm_sol_frac"]
    assert rows[0]["value"] == 0.5 and rows[0]["unit"] == "frac of SOL"
    assert rows[0]["backend"] == "tpu" and rows[0]["ndev"] == 8
    # the CI-gate subset stays inside the report's actual row names
    assert set(GATE_OPS) <= {
        "ag_gemm", "gemm_rs", "gemm_allreduce", "flash_decode",
        "flash_decode_paged", "ag_group_gemm", "moe_reduce_rs",
        "moe_reduce_ar", "ep_fused", "gdn_fwd(pallas)"}


# ----------------------------------------------------------------------
# slow arms: the merged cross-plane trace through a THREADED
# disaggregated TokenServer (the acceptance-criteria run) and the
# disagg trace-on == trace-off bitwise differential
# ----------------------------------------------------------------------

@pytest.mark.slow
def test_token_server_disagg_merged_trace(tmp_path, monkeypatch):
    """TokenServer(disagg=True, prefill_workers=2,
    disagg_threads=True) under TDTPU_TRACE: the dumped trace is ONE
    merged timeline — decode-plane poll/device spans, per-worker
    prefill tracks, and a complete flow chain joining each request's
    kv_push to its kv_install across planes — and the traced server's
    streams are byte-identical to an untraced run's."""
    from triton_dist_tpu.serving import (ByteTokenizer, TokenServer,
                                         request_stream)
    cfg, eng = _engine()
    tok = ByteTokenizer(cfg.vocab_size)
    prompts = ["interactive req", "batch workload!", "third one"]
    slos = ["interactive", "batch", None]

    def serve(trace):
        srv = TokenServer(eng, tok, batch=2, chunk=2, disagg=True,
                          prefill_workers=2, disagg_threads=True,
                          trace=trace)
        th = threading.Thread(target=srv.serve_forever,
                              kwargs=dict(max_requests=len(prompts)),
                              daemon=True)
        th.start()
        outs = {}
        for i, p in enumerate(prompts):
            toks = []
            for msg in request_stream(srv.host, srv.port, p,
                                      gen_len=6, seed=3 + i,
                                      slo=slos[i]):
                toks.extend(msg.get("token_ids", []))
            outs[p] = toks
        th.join(timeout=120)
        srv.stop()
        return outs, srv

    ref, _ = serve(trace=False)
    trace_path = str(tmp_path / "disagg_trace.json")
    monkeypatch.setenv("TDTPU_TRACE", trace_path)
    got, srv = serve(trace=None)        # env convention: trace + dump
    assert got == ref, "disagg streams diverged trace-on vs off"

    with open(trace_path) as fh:
        dump = json.load(fh)
    evs = dump["traceEvents"]
    tracks = {e["args"]["name"] for e in evs if e.get("ph") == "M"
              and e.get("name") == "thread_name"}
    workers = {t for t in tracks if t.startswith("prefill-worker-")}
    assert workers, f"no worker tracks in {sorted(tracks)}"
    names = {e.get("name") for e in evs if e.get("ph") == "X"}
    assert {"poll", "prefill:compute", "kv_push",
            "kv_install"} <= names
    starts = [e for e in evs if e.get("ph") == "s"]
    ends = [e for e in evs if e.get("ph") == "f"]
    assert len(ends) == len(prompts)
    assert {e["id"] for e in ends} <= {e["id"] for e in starts}
    # one request's journey crosses BOTH planes: its flow chain has
    # host-track ends and a worker-track step
    wtids = {e["tid"] for e in evs if e.get("ph") == "M"
             and e.get("args", {}).get("name", "") in workers}
    fid = ends[0]["id"]
    chain_tids = {e["tid"] for e in evs
                  if e.get("ph") in ("s", "t", "f")
                  and e.get("id") == fid}
    assert 0 in chain_tids and chain_tids & wtids

    # SLO accounting surfaced end-to-end through the server
    st = srv.stats()
    assert (st["slo_goodput{slo=interactive}"]
            + st["slo_violations{slo=interactive}"]) == 1
    assert (st["slo_goodput{slo=batch}"]
            + st["slo_violations{slo=batch}"]) == 1
    assert st["staging_pages_resident"] == 0    # zero-leak, visible
    assert st["staging_pages_peak"] > 0

    # the merged timeline renders (text + --json) with per-plane time
    tv = _load_tool("trace_view")
    a = tv.analyze(dump)
    assert any(p.startswith("prefill-worker-") for p in a["planes"])
    assert any(fl["complete"] for fl in a["flows"])
    text = tv.summarize(dump)
    assert "flows:" in text and "prefill-worker-" in text


@pytest.mark.slow
def test_disagg_trace_bitwise_with_slo():
    """(slow: obs_smoke runs it.) Scheduler-level disagg arm: trace-on
    == trace-off bitwise with SLO-tagged requests in the mix, inline
    workers (deterministic)."""
    import dataclasses

    from triton_dist_tpu.models import DisaggScheduler
    cfg, eng = _engine()
    rng = np.random.RandomState(11)
    reqs = [Request(rid=i,
                    ids=rng.randint(0, cfg.vocab_size,
                                    size=(L,)).astype(np.int32),
                    gen_len=g, seed=50 + i,
                    slo="interactive" if i % 2 else "batch")
            for i, (L, g) in enumerate([(5, 6), (14, 8), (3, 4)])]

    def run(trace):
        sched = DisaggScheduler(eng, batch=3, chunk=4, trace=trace)
        try:
            return sched.run([dataclasses.replace(r) for r in reqs])
        finally:
            sched.close()

    ref, got = run(False), run(True)
    for rid in ref:
        np.testing.assert_array_equal(got[rid], ref[rid])


@pytest.mark.parametrize("kw,tick", [
    ({}, {"sched:dispatch", "sched:land"}),
    # the control: the synchronous poll's one `step` round its tick
    (dict(overlap=False), {"sched:step"})], ids=["default", "sync"])
def test_profiler_trace_holds_the_host_phases(tmp_path, kw, tick):
    """An operator's `jax.profiler` session round a live TokenServer
    (trace off) sees the serve loop's and the scheduler's phases as
    `serve:` / `sched:` annotations on a host line, i.e. on the clock
    the device's planes are on."""
    import glob
    from jax.profiler import ProfileData
    from triton_dist_tpu.serving import (ByteTokenizer, TokenServer,
                                         request_stream)
    cfg, eng = _engine()
    srv = TokenServer(eng, ByteTokenizer(cfg.vocab_size), batch=2,
                      chunk=4, paged=True, page=8, **kw)
    th = threading.Thread(target=srv.serve_forever,
                          kwargs=dict(max_requests=2), daemon=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        th.start()
        for p in ("first prompt", "second"):
            toks = [t for msg in request_stream(srv.host, srv.port, p,
                                                gen_len=8)
                    for t in msg.get("token_ids", [])]
            assert len(toks) == 8
        th.join(timeout=120)
    finally:
        jax.profiler.stop_trace()
        srv.stop()
    (xplane,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                              / "*.xplane.pb"))
    want = {"serve:loop", "serve:accept_wait", "serve:poll",
            "serve:wire_write", "sched:poll", "sched:admit",
            "sched:device_wait"} | tick
    lines = [{e.name for e in line.events}
             for plane in ProfileData.from_file(xplane).planes
             if plane.name.startswith("/host:")
             for line in plane.lines]
    assert any(want <= names for names in lines), \
        [sorted(n for n in names if ":" in n) for names in lines]
