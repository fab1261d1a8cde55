"""The phi4flash work functions against hand counts at the published
sizes (the self-check's style: every expected number is worked out here
from the configuration's file, not taken from the function), and the new
metrics' data files against the readers they name."""

import json
import os

import numpy as np
import pytest

from benchmark import harness, spec
from benchmark.work import phi4flash_attn, phi4flash_ssm, phi4flash_step

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "phi4-mini-flash.reasoning-saturated"


@pytest.fixture(scope="module")
def m():
    return spec.Benchmark(_ROOT).config("phi4-mini-flash")


def test_layer_kinds_and_parameters(m):
    assert phi4flash_step.kinds(m) == dict(mamba=9, swa=8, full=1,
                                           cross=7, gmu=7)
    mp = phi4flash_step.mix_params(m)
    # in_proj 2560 x 10240, x_proj 5120 x 192, dt 160 x 5120, out 5120 x 2560
    assert mp["mamba"] == 26214400 + 983040 + 819200 + 13107200
    assert mp["swa"] == mp["full"] == 2560 * 5120 + 2560 * 2560
    assert mp["cross"] == 2 * 2560 * 2560
    assert mp["gmu"] == 2 * 2560 * 5120
    assert phi4flash_step.mlp_params(m) == 78643200
    # the issue's 3.852 B: layers 3,340 M (less the small vectors) + head
    total = (9 * mp["mamba"] + 9 * mp["swa"] + 7 * mp["cross"]
             + 7 * mp["gmu"] + 32 * 78643200)
    assert total == 3338895360
    assert phi4flash_step.weight_bytes(m) == 2 * (total + 2560 * 200064)


def test_attention_and_state_counts(m):
    # 20 pairs x 2 softmaxes x (2 x 64 QK + 2 x 128 PV)
    assert phi4flash_step.attn_flops_per_key(m) == 15360
    assert phi4flash_step.kv_bytes_per_position(m) == 5120
    # 9 layers x (3 + 16) x 5120 x 4 B
    assert phi4flash_step.state_bytes_per_slot(m) == 3502080
    assert phi4flash_step.scan_flops_per_token(m) == 6 * 81920 + 8 * 5120


def test_decode_step_bytes_at_the_issues_shape(m):
    """Batch 64 at a mean context of 3,000: weights 7.7 GB, layer 17's
    pool eight times 7.9 GB, rings 1.3 GB, states 0.4 GB."""
    ctx = np.full((64,), 3000)
    assert abs(phi4flash_step.weight_bytes(m) - 7.70e9) < 0.01e9
    b = phi4flash_step.decode_token_bytes(m, ctx)
    pool = 8 * 64 * 3000 * 5120
    rings = 8 * 64 * 512 * 5120
    state = 2 * 64 * 3502080
    assert b == pool + rings + state
    assert abs(pool - 7.86e9) < 0.01e9 and abs(rings - 1.34e9) < 0.01e9
    floor_ms = 1e3 * (phi4flash_step.weight_bytes(m) + b) / 819e9
    assert 20.5 < floor_ms < 21.5                  # the issue's 21 ms


def test_window_flops_by_hand(m):
    tok = phi4flash_step.token_flops(m)
    assert tok == 2 * 3338895360 + 9 * (6 * 81920 + 8 * 5120)
    pre = phi4flash_step.prompt_token_flops(m)
    assert pre == (2 * (9 * 41123840 + 8 * 19660800) + 2 * 17 * 78643200
                   + 9 * 532480 + 2 * 2560 * 2560)
    # one prompt of 2,048 and its first token at context 2,048
    got = phi4flash_step.window_flops(m, prompt_lens=[2048],
                                      contexts=[2048])
    swa_keys = 512 * 513 // 2 + (2048 - 512) * 512
    want = (pre * 2048 + 15360 * 8 * swa_keys
            + tok + 2 * 2560 * 200064
            + 15360 * (8 * 2048 + 8 * 512))
    assert got == want
    # a window layer never counts more than its window
    short = phi4flash_step.window_flops(m, prompt_lens=[], contexts=[100])
    assert short == tok + 2 * 2560 * 200064 + 15360 * 16 * 100


def test_kernel_work_by_hand(m):
    a = phi4flash_attn.work(m, 1, steps=2, kv_tokens_per_step=64 * 3000,
                            rows_per_step=64)
    keys = 8 * 64 * 3000 + 8 * 64 * 512
    assert a["flops"] == 2 * 15360 * keys
    assert a["hbm_bytes"] == 2 * (5120 * keys + 16 * 2 * 2560 * 2 * 64)
    s = phi4flash_ssm.work(m, 1, steps=2, rows_per_step=64)
    assert s["hbm_bytes"] == 2 * 9 * 64 * (2 * 16 * 5120 * 4
                                           + (3 * 5120 + 32) * 4)
    assert s["flops"] == 2 * 9 * 64 * 6 * 81920
    assert a["ici_bytes"] == s["ici_bytes"] == 0.0


def test_cell_and_metrics_are_wired(m):
    bench = spec.Benchmark(_ROOT)
    wl = bench.workload(CELL)
    assert (wl["chips"], wl["traffic"]) == (1, "reasoning-saturated")
    mix = bench.traffic(wl["traffic"])
    assert mix["clients"] == 96 and mix["prompt_len"]["values"] == [2048]
    assert bench.limits(CELL)["max_gap"] > 0
    names = [x["name"] for x in bench.metrics_for(CELL, trace=True)]
    assert "step_mfu.reason" in names and len(names) == 8
    assert [x["name"] for x in bench.metrics_for(CELL, trace=False)] == [
        "out_tokens_per_s", "setup_s"]
    # every catalog key is in the file as published
    assert (m["hidden_size"], m["num_hidden_layers"], m["sliding_window"],
            m["reduced"]) == (2560, 32, 512, [])


def _capture(m, records, stats1):
    return harness.Capture(
        workload={}, config=m, mix={}, chips=1, seconds=40.0, setup_s=0.0,
        peaks={"flops_per_s": {"bfloat16": 197e12},
               "hbm_bytes_per_s": 819e9},
        t0=0.0, t1=40.0, drain_end=40.0, records=records, stats0={},
        stats1=stats1, lifecycle={}, trace=None, batch=64, chunk=4)


def test_readers_on_a_hand_made_capture(m):
    from benchmark import load
    r = load.Record(index=0, prompt=np.zeros(2048, np.int32), gen_len=8,
                    due=0.0, first=1.0)
    r.token_times = [(1.0, 1), (2.0, 4), (41.0, 3)]     # the last is late
    bench = spec.Benchmark(_ROOT)
    cap = _capture(m, [r], {
        "cache_bytes{kind=pages}": 16.0, "cache_bytes{kind=window}": 21.0,
        "cache_bytes{kind=state}": 3.0, "cache_uniform_bytes": 400.0})
    from benchmark.readers import window_contexts
    prompts, ctx = window_contexts.read(cap)
    assert prompts.tolist() == [2048]
    assert ctx.tolist() == [2048, 2049, 2050, 2051, 2052]
    mfu = bench.read_metric("step_mfu.reason", cap)
    want = 100.0 * phi4flash_step.window_flops(
        m, prompt_lens=[2048], contexts=ctx) / (40.0 * 197e12)
    assert mfu == pytest.approx(want)
    assert bench.read_metric("cache.state_bytes_pct.reason", cap) == 10.0
    # a program without the gauges, a run without a trace: nothing, no raise
    old = _capture(m, [r], {})
    assert bench.read_metric("cache.state_bytes_pct.reason", old) is None
    for name in ("step.hbm_floor_pct.reason", "ssm_step_roofline.reason",
                 "decode_attn_roofline.reason",
                 "tick.decode_step_ms.reason", "device.idle_pct.reason"):
        assert bench.read_metric(name, old) is None


def test_trace_patterns_name_what_the_model_emits():
    """The attention walks are named after their layer's scope, the
    state update after its kernel (compile, PR 31: `%swa.3 = bf16[640,4,
    128]... custom-call(`, `%ssm_step.63 = (f32[64,5120]..., f32[64,16,
    5120]...) custom-call(`); the MLP's fused SwiGLU must not match."""
    import re

    def pats(name):
        with open(os.path.join(_ROOT, "benchmark", "metrics",
                               name + ".json")) as f:
            return [re.compile(p) for p in json.load(f)["args"]["patterns"]]

    attn, ssm = pats("decode_attn_roofline.reason"), \
        pats("ssm_step_roofline.reason")
    hit = lambda rx, s: any(r.search(s) for r in rx)  # noqa: E731
    for scope in ("swa", "full", "cross"):
        assert hit(attn, f"%{scope}.12 = bf16[640,4,128]{{2,1,0:T(4,128)"
                         f"(2,1)S(1)}} custom-call(%x)")
    assert not hit(attn, "%closed_call.3 = bf16[64,10240]{1,0} "
                         "custom-call(%y)")
    assert not hit(attn, "%ssm_step.63 = (f32[64,5120]{1,0}, f32[64,16,"
                         "5120]{2,1,0}) custom-call(%z)")
    assert hit(ssm, "%ssm_step.63 = (f32[64,5120]{1,0}, f32[64,16,5120]"
                    "{2,1,0}) custom-call(%z)")
    assert not hit(ssm, "%swa.12 = bf16[640,4,128]{2,1,0} custom-call(")
