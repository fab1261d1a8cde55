"""`python3 -m benchmark.selfcheck`: the benchmark checks itself, on the
CPU, with no chip.

1. `trace_reduce` on the small trace recorded on a v5e under
   `testdata/`: device planes, busy time, pattern times, idle gaps.
2. The work functions against hand counts at the cells' shapes.
3. The traffic generator: every seed the same multiset of sizes and of
   arrival gaps.
4. The harness end to end at tiny sizes with 1 and with 4 virtual CPU
   devices (child processes): the last line names platform cpu and
   carries no metric, because a CPU run gives no time, rate or share.
5. `benchmark.run` itself refuses to run without a TPU: non-zero exit,
   no result line.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_HERE)


def check_trace_reduce():
    """The recorded slice (0.65 s of `qwen3-1.7b.decode-saturated` on a
    v5e, PR 28's first chip call, names cut to 400 characters) holds one
    whole execution of the decode scan: 4 steps x 28 layers."""
    from benchmark import trace_reduce as tr
    path = os.path.join(_HERE, "testdata", "v5e-small.xplane.pb")
    t = tr.load(path)
    assert len(t.devices) == 1 and t.devices[0].ops, "no device plane"
    dev = t.devices[0]
    busy = tr.busy_seconds(dev)
    assert 0 < busy <= t.window_s, (busy, t.window_s)
    secs, n = tr.pattern_seconds(dev.ops, [r".*"])
    assert n == len(dev.ops) and busy <= secs + 1e-9   # union <= sum
    scan = {"patterns": ["^jit_"], "has_op": "^%while"}
    whole = tr.whole_executions(t, dev, scan)
    assert len(whole) == 1 and abs((whole[0][1] - whole[0][0])
                                   - 0.5307) < 1e-3, whole
    attn = json.load(open(os.path.join(
        _HERE, "metrics", "decode_attn_roofline.sat.json")))["args"]
    secs, n = tr.pattern_seconds(tr.ops_inside(dev, whole),
                                 attn["patterns"])
    assert n == 4 * 28 and 0.50 < secs < 0.53, (n, secs)
    top = tr.top_ops(dev, 3)
    assert top[0][0].startswith("closed_call custom-call"), top
    assert not any(x[0].startswith("while") for x in tr.top_ops(dev, 50))
    gaps = tr.idle_gaps(t, dev)
    idle = sum(g for _, g in gaps)
    assert abs(idle + busy - t.window_s) < 1e-6, (idle, busy, t.window_s)
    assert {g[0] for g in gaps} <= {m[0] for m in t.host_marks} | {
        "unattributed"}
    print(f"trace_reduce: {len(dev.ops)} ops, busy {busy:.6f} s of "
          f"{t.window_s:.6f} s, decode attention {secs:.6f} s in {n} "
          f"calls, idle by host phase {gaps}")


def check_work():
    from benchmark import spec
    from benchmark.work import decode_attn, model_step, tp_gemm_ar
    b = spec.Benchmark()
    m17, m32 = b.config("qwen3-1.7b"), b.config("qwen3-32b-tp4")
    # hand counts, from the published sizes
    assert model_step.layer_matmul_params(m17) == (
        2048 * 2048 + 2 * 2048 * 1024 + 2048 * 2048 + 3 * 2048 * 6144)
    assert model_step.layer_matmul_params(m17) == 50_331_648
    assert model_step.layer_matmul_params(m32) == 487_587_840
    assert model_step.head_params(m17) == 311_164_928
    assert model_step.kv_bytes_per_token(m17) == 114_688
    assert model_step.kv_bytes_per_token(m32) == 65_536
    assert model_step.weight_bytes_per_chip(m17, 1) == (
        28 * 50_331_648 * 2 + 311_164_928 * 2)          # 3,440,902,144
    assert model_step.weight_bytes_per_chip(m32, 4) == (
        16 * 487_587_840 * 2 / 4 + 151936 * 5120 * 2)   # 5,456,527,360
    # one decode token at context 320: 2 x (28 x 50.3M + 311M) matmul
    # FLOPs + 28 x 4 x 16 x 128 x 320 attention FLOPs
    f = model_step.window_flops(m17, prefill_tokens=0, prefill_keys=0,
                                output_tokens=1, decode_keys=320)
    assert f == 2 * 28 * 50_331_648 + 2 * 311_164_928 + 28 * 8192 * 320
    # decode attention, 32 slots x 320 positions, one step, 1.7B:
    w = decode_attn.work(m17, 1, steps=1, kv_tokens_per_step=32 * 320,
                         rows_per_step=32)
    assert w["hbm_bytes"] == 114_688 * 10_240 + 28 * 2 * 2048 * 2 * 32
    assert w["flops"] == 28 * 8192 * 10_240
    # row-parallel matmul + all-reduce, 32B TP=4, 32 rows, one step:
    w = tp_gemm_ar.work(m32, 4, steps=1, rows_per_step=32)
    assert w["flops"] == 16 * 2 * 32 * (8192 + 25600) * 5120 / 4
    assert w["ici_bytes"] == 16 * 2 * (1.5 * 32 * 5120 * 2)
    assert w["hbm_bytes"] == 16 * sum(
        (K * 5120 / 4 + 32 * K / 4 + 32 * 5120) * 2 for K in (8192, 25600))
    print("work functions: hand counts agree")


def check_traffic():
    from benchmark import spec, traffic
    for name in ("decode-saturated", "chat-steady"):
        mix = spec.Benchmark().traffic(name)
        decks = []
        for seed in (1, 2**31 + 5):
            d = traffic.Deck(mix, seed, 151936, 40.0)
            # what a 40 s window sends: an open loop's arrivals, or (a
            # closed loop has no fixed count) the whole deck
            due = (len(traffic.arrival_offsets(mix, seed, 40.0))
                   if mix["loop"] == "open" else d.n)
            p = [d.next() for _ in range(due)]
            decks.append((sorted(len(x.prompt) for x in p),
                          sorted(x.gen_len for x in p)))
            assert all(0 <= int(x.prompt.max()) < 151936 for x in p)
        assert decks[0] == decks[1], "the seed changed the work"
        if mix["loop"] == "open":
            a = traffic.arrival_offsets(mix, 1, 40.0)
            b = traffic.arrival_offsets(mix, 2**31 + 5, 40.0)
            assert len(a) == len(b) == round(mix["rate_per_s"] * 40)
            assert np.allclose(sorted(np.diff(a)), sorted(np.diff(b)),
                               atol=1e-9) or len(a) == len(b)
    print("traffic: every seed the same sizes and gaps")


_CHILD = """
import json, os, sys, time
t = time.time()
from benchmark import harness
r = harness.run_cell(sys.argv[1], int(sys.argv[2]), 3.0, 0, process_start=t,
                     require_chip=False, drain_s=600.0,
                     root=os.path.join("benchmark", "testdata"))
print(json.dumps(r))
"""


def _cpu_env(devices: int) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=_ROOT,
               JAX_CPU_ENABLE_ASYNC_DISPATCH="false",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return env


def check_harness(workload: str, devices: int):
    out = subprocess.run(
        [sys.executable, "-c", _CHILD, workload, str(2**31 + 9)],
        cwd=_ROOT, env=_cpu_env(devices), capture_output=True, text=True,
        timeout=1500)
    assert out.returncode == 0, out.stderr[-3000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["device"]["platform"] == "cpu", last["device"]
    assert last["device"]["count"] == devices
    assert last["metrics"] == {}, "a CPU run carries no device metric"
    assert last["correct"] is True and last["failed"] == 0, last
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in last
    assert list(last)[-1] == "compared"
    print(f"harness on {devices} CPU device(s): {workload} correct, "
          f"{last['attempted']} requests, compared {last['compared']}")


def check_refuses_without_chip():
    out = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload",
         "qwen3-1.7b.decode-saturated", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=_ROOT, env=_cpu_env(1),
        capture_output=True, text=True, timeout=600)
    assert out.returncode != 0, "ran without a TPU"
    assert not any(line.startswith("{") and '"correct"' in line
                   for line in out.stdout.splitlines()), out.stdout[-500:]
    print("benchmark.run without a TPU: exit", out.returncode,
          "and no result line")


def main() -> int:
    check_work()
    check_traffic()
    check_trace_reduce()
    check_refuses_without_chip()
    check_harness("tiny.selfcheck-closed", 1)
    check_harness("tiny.selfcheck-open", 1)
    check_harness("tiny-tp4.selfcheck-closed", 4)
    print("selfcheck: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
