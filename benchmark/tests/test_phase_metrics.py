"""The per-layer metrics that read the program's own phases and
lifecycle (`host_phase_s`, `serve_loop_iterations`, `accepted` ..
`first_token`), through their data files, on hand-made captures with
hand-worked values; and `BENCHMARK.json` with a file behind every
metric it names."""

import importlib
import json
import os

import pytest

from benchmark import harness, spec

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _capture(stats0, stats1, lifecycle=None, records=(), seconds=40.0):
    return harness.Capture(
        workload={}, config={}, mix={}, peaks=None, chips=1,
        seconds=seconds, setup_s=0.0, t0=0.0, t1=seconds,
        drain_end=seconds, records=list(records), stats0=stats0,
        stats1=stats1, lifecycle=lifecycle or {}, trace=None, batch=32,
        chunk=4)


def _phases(**kw):
    return {"host_phase_s": kw}


S0 = dict(_phases(loop=0.1, accept_wait=1.0, poll=0.2, admit=0.5,
                  device_wait=10.0, idle_sleep=0.0),
          serve_loop_iterations=20,
          **{"host_phase_n{phase=idle_sleep}": 0,
             "host_phase_n{phase=loop}": 20})
S1 = dict(_phases(loop=0.3, accept_wait=3.0, poll=0.6, admit=0.9,
                  device_wait=46.0, idle_sleep=0.4),
          serve_loop_iterations=80,
          **{"host_phase_n{phase=idle_sleep}": 8,
             "host_phase_n{phase=loop}": 80})

# [ms, name, detail] as Telemetry.export()["requests"] has them
LIFE = {
    "0": {"events": [[10.0, "accepted", None], [14.0, "queued", None],
                     [90.0, "admitted", 0], [700.0, "first_token", 4],
                     [701.0, "wire_first", 4], [1300.0, "tokens", 4]]},
    "1": {"events": [[20.0, "accepted", None], [22.0, "queued", None],
                     [95.0, "admitted", 1], [725.0, "first_token", 4],
                     [726.0, "wire_first", 4]]},
    "2": {"events": [[30.0, "accepted", None], [630.0, "queued", None],
                     [640.0, "admitted", 2], [1300.0, "first_token", 4]]},
}


@pytest.mark.parametrize("metric, want", [
    ("serve.accept_wait_pct.sat", 100.0 * 2.0 / 40.0),
    ("serve.accept_wait_pct.steady", 5.0),
    ("sched.admit_host_pct.sat", 100.0 * 0.4 / 40.0),
    # all but accept_wait, admit, device_wait, idle_sleep: loop + poll
    ("serve.host_other_pct.sat", 100.0 * (0.2 + 0.4) / 40.0),
    # all but accept_wait and idle_sleep, per iteration:
    # (0.2 + 0.4 + 0.4 + 36.0) s / 60
    ("serve.accept_gap_ms.steady", 1e3 * 37.0 / 60.0),
    # numpy's linear percentile over [2, 4, 600] and [610, 630, 660]
    ("serve.accept_to_queued_p95_ms.steady", 4.0 + 0.9 * 596.0),
    ("sched.admit_to_first_token_p95_ms.steady", 630.0 + 0.9 * 30.0),
])
def test_metric_reads_hand_made_capture(metric, want):
    cap = _capture(S0, S1, LIFE)
    assert spec.Benchmark().read_metric(metric, cap) == pytest.approx(want)


def test_the_three_host_shares_add_up_to_host_share():
    """accept_wait + admit + the rest = everything but the device wait
    and the idle sleep, which is what `sched.host_share_pct.sat` reads
    from `device_wait_s_by_kind` where no iteration sleeps."""
    s1 = dict(S1, host_phase_s=dict(S1["host_phase_s"], idle_sleep=0.0))
    cap = _capture(S0, s1)
    b = spec.Benchmark()
    parts = sum(b.read_metric(m, cap) for m in (
        "serve.accept_wait_pct.sat", "sched.admit_host_pct.sat",
        "serve.host_other_pct.sat"))
    assert parts == pytest.approx(100.0 * (3.0 / 40.0))


@pytest.mark.parametrize("metric", [
    "serve.accept_wait_pct.sat", "serve.accept_wait_pct.steady",
    "sched.admit_host_pct.sat", "serve.host_other_pct.sat",
    "serve.accept_gap_ms.steady", "serve.accept_to_queued_p95_ms.steady",
])
def test_a_program_without_phases_reads_nothing(metric):
    """The parent commit has neither the totals nor `accepted`: the
    readers return None and do not raise."""
    life = {"0": {"events": [[1.0, "queued", None], [2.0, "admitted", 0],
                             [9.0, "first_token", 4]]}}
    cap = _capture({"tokens_emitted": 1}, {"tokens_emitted": 9}, life)
    assert spec.Benchmark().read_metric(metric, cap) is None


def test_benchmark_json_has_a_file_for_every_metric():
    b = spec.Benchmark()
    cells = {w["name"] for w in b.doc["workloads"]}
    e2e = {m["name"] for m in b.doc["end_to_end"]}
    names = [m["name"] for m in b.doc["end_to_end"] + b.doc["per_layer"]]
    assert len(names) == len(set(names))
    for m in b.doc["end_to_end"] + b.doc["per_layer"]:
        with open(os.path.join(_ROOT, "benchmark", "metrics",
                               m["name"] + ".json")) as f:
            entry = json.load(f)
        importlib.import_module("benchmark.readers." + entry["reader"])
        assert set(m.get("workloads", ())) <= cells
    for m in b.doc["per_layer"]:
        assert m["moves"] in e2e
    # this PR's seven, at the end of the list
    assert [m["name"] for m in b.doc["per_layer"][-7:]] == [
        "serve.accept_wait_pct.sat", "serve.accept_wait_pct.steady",
        "sched.admit_host_pct.sat", "serve.host_other_pct.sat",
        "serve.accept_gap_ms.steady",
        "serve.accept_to_queued_p95_ms.steady",
        "sched.admit_to_first_token_p95_ms.steady"]
