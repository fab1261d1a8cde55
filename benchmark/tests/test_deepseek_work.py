"""The deepseek work functions against hand counts at the published
widths (every expected number is worked out here from the
configuration's file and ISSUE 36's table, not taken from the function),
and the new cell's data files against the readers they name."""

import json
import os
import re

import numpy as np
import pytest

from benchmark import harness, spec
from benchmark.work import deepseek_mla, deepseek_moe, deepseek_step

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "deepseek-v3-ep16.longgen-saturated"
MLA = 187105280            # 7168x1536 + 1536x24576 + 7168x576 + 512x32768 + 16384x7168
DENSE = 396361728          # 3 x 7168 x 18432
EXPERT = 44040192          # 3 x 7168 x 2048
ROUTER = 1835008           # 7168 x 256
HEAD = 16160 * 7168


@pytest.fixture(scope="module")
def m():
    return spec.Benchmark(_ROOT).config("deepseek-v3-ep16")


def test_parameters_by_the_issues_table(m):
    assert 7168 * 1536 + 1536 * 24576 + 7168 * 576 + 512 * 32768 \
        + 16384 * 7168 == MLA
    assert deepseek_step.kinds(m) == dict(dense=1, moe=4)
    assert deepseek_step.mla_params(m) == MLA
    assert deepseek_step.dense_mlp_params(m) == DENSE
    assert deepseek_step.expert_params(m) == EXPERT
    assert deepseek_step.shared_params(m) == EXPERT
    assert deepseek_step.router_params(m) == ROUTER
    lp = deepseek_step.layer_params(m)
    assert lp["dense"] == 583467008
    assert lp["moe"] == 937623552 == MLA + EXPERT + ROUTER + 16 * EXPERT
    # the table's 4,565,630,976 holds the embedding too, which a decode
    # step does not read (a row a token)
    assert 583467008 + 4 * 937623552 + 2 * HEAD == 4565630976
    assert deepseek_step.weight_bytes(m) == 2 * (4565630976 - HEAD)


def test_routed_share_and_attention_counts(m):
    assert deepseek_step.held_pairs_per_token(m) == 0.5       # 8 x 16/256
    assert deepseek_step.latent_row_bytes(m) == 1152
    assert deepseek_step.decode_attn_flops_per_key(m) == 278528
    assert deepseek_step.prefill_attn_flops_per_key(m) == 81920
    # 242 FLOP a byte, against the chip's 197e12 / 819e9 = 240.5
    assert 241 < 278528 / 1152 < 242.5


def test_decode_step_at_the_issues_shape(m):
    """128 slots at a context of 2,000: 8.9 GB of weights of which 5.6
    are the held experts', 1.5 GB of latent rows at ~360 GFLOP."""
    ctx = np.full((128,), 2000)
    w = deepseek_step.weight_bytes(m)
    assert abs(w - 8.90e9) < 0.01e9
    assert abs(4 * 16 * EXPERT * 2 - 5.64e9) < 0.01e9
    b = deepseek_step.decode_token_bytes(m, ctx)
    assert b == 5 * 128 * 2000 * 1152 and abs(b - 1.47e9) < 0.01e9
    flops = 5 * 128 * 2000 * 278528
    assert abs(flops - 357e9) < 1e9
    assert 12.5 < 1e3 * (w + b) / 819e9 < 12.8          # the step's floor


def test_window_flops_by_hand(m):
    tok = deepseek_step.token_flops(m)
    assert tok == 2 * (MLA + DENSE) + 4 * 2 * (
        MLA + EXPERT + ROUTER + 0.5 * EXPERT)
    # the issue's ~3.4 GFLOP a token holds the head's 0.23
    assert 3.4e9 < tok + 2 * HEAD < 3.5e9
    got = deepseek_step.window_flops(m, prompt_lens=[1024],
                                     contexts=[1024, 1025])
    want = (tok * 1024 + 81920 * 5 * (1024 * 1025 // 2)
            + 2 * (tok + 2 * HEAD) + 278528 * 5 * (1024 + 1025))
    assert got == want


def test_kernel_work_by_hand(m):
    a = deepseek_mla.work(m, 1, steps=2, kv_tokens_per_step=128 * 2000,
                          rows_per_step=128)
    assert a["flops"] == 2 * 5 * 278528 * 128 * 2000
    assert a["hbm_bytes"] == 2 * 5 * (
        1152 * 128 * 2000 + 128 * 128 * (576 + 512) * 2)
    g = deepseek_moe.work(m, 1, steps=2, rows_per_step=128)
    pairs = 128 * 0.5
    assert g["flops"] == 2 * 4 * 2 * EXPERT * pairs
    assert g["hbm_bytes"] == 2 * 4 * (
        16 * EXPERT * 2 + pairs * 2 * (7168 + 2048) * 2)
    # the held experts' own read is what bounds it: 1.72 ms a layer
    assert 1.71 < 1e3 * (g["hbm_bytes"] / 8) / 819e9 < 1.73
    assert a["ici_bytes"] == g["ici_bytes"] == 0.0


def test_cell_and_metrics_are_wired(m):
    bench = spec.Benchmark(_ROOT)
    wl = bench.workload(CELL)
    assert (wl["chips"], wl["traffic"]) == (1, "longgen-saturated")
    mix = bench.traffic(wl["traffic"])
    assert mix["clients"] == 192 and mix["prompt_len"]["values"] == [1024]
    assert (mix["output_len"]["lo"], mix["output_len"]["hi"]) == (512, 3000)
    # `mean_gap` decides this cell; the maximum does not separate the
    # precisions (routing is discrete) and has no limit: the file's why
    lim = bench.limits(CELL)
    assert lim["mean_gap"] > 0 and "max_gap" not in lim
    assert "why_no_max_gap" in lim["readings"]
    names = [x["name"] for x in bench.metrics_for(CELL, trace=True)]
    assert sorted(names) == sorted(
        n + ".longgen" for n in (
            "step_mfu", "step.hbm_floor_pct", "tick.decode_step_ms",
            "device.idle_pct", "sched.host_share_pct",
            "sched.dispatch_ahead_pct", "mla_decode_roofline",
            "moe_gmm_roofline", "moe.held_pair_share_pct"))
    assert [x["name"] for x in bench.metrics_for(CELL, trace=False)] == [
        "out_tokens_per_s", "setup_s"]
    # every width as published; the five cuts and no other
    assert (m["hidden_size"], m["kv_lora_rank"], m["q_lora_rank"],
            m["moe_intermediate_size"], m["num_experts_per_tok"],
            m["n_group"], m["topk_group"]) == (7168, 512, 1536, 2048, 8,
                                               8, 4)
    assert m["reduced"] == ["num_hidden_layers", "first_k_dense_replace",
                            "n_routed_experts", "vocab_size",
                            "num_nextn_predict_layers"]
    assert m["deployment"]["chips_per_layer"] * m["n_routed_experts"] \
        == m["deployment"]["routed_experts_total"] == 256
    assert m["server"]["batch"] == 128 and m["engine"]["max_seq"] == 4096


def _capture(m, records, stats0, stats1):
    return harness.Capture(
        workload={}, config=m, mix={}, chips=1, seconds=40.0, setup_s=0.0,
        peaks={"flops_per_s": {"bfloat16": 197e12},
               "hbm_bytes_per_s": 819e9},
        t0=0.0, t1=40.0, drain_end=40.0, records=records, stats0=stats0,
        stats1=stats1, lifecycle={}, trace=None, batch=128, chunk=4)


def test_readers_on_a_hand_made_capture(m):
    from benchmark import load
    r = load.Record(index=0, prompt=np.zeros(1024, np.int32), gen_len=8,
                    due=0.0, first=1.0)
    r.token_times = [(1.0, 1), (2.0, 4), (41.0, 3)]     # the last is late
    bench = spec.Benchmark(_ROOT)
    cap = _capture(m, [r], {"moe_pairs_routed": 1000, "moe_pairs_held": 60},
                   {"moe_pairs_routed": 9000, "moe_pairs_held": 560})
    mfu = bench.read_metric("step_mfu.longgen", cap)
    want = 100.0 * deepseek_step.window_flops(
        m, prompt_lens=[1024], contexts=np.arange(1024, 1029)) / (
            40.0 * 197e12)
    assert mfu == pytest.approx(want)
    assert bench.read_metric("moe.held_pair_share_pct.longgen",
                             cap) == 6.25
    # a program without the counters (the parent), a run without a
    # trace: nothing, and no raise
    old = _capture(m, [r], {}, {})
    assert bench.read_metric("moe.held_pair_share_pct.longgen",
                             old) is None
    for name in ("step.hbm_floor_pct.longgen", "mla_decode_roofline.longgen",
                 "moe_gmm_roofline.longgen", "tick.decode_step_ms.longgen",
                 "device.idle_pct.longgen"):
        assert bench.read_metric(name, old) is None


def test_trace_patterns_name_what_the_model_emits():
    """The walk takes its `jax.named_scope`, the grouped GEMMs their
    kernel's `name=`; the shared expert's fused SwiGLU and the absorb
    products must not match."""
    def pats(name):
        with open(os.path.join(_ROOT, "benchmark", "metrics",
                               name + ".json")) as f:
            return [re.compile(p) for p in json.load(f)["args"]["patterns"]]

    mla, gmm = pats("mla_decode_roofline.longgen"), \
        pats("moe_gmm_roofline.longgen")
    hit = lambda rx, s: any(r.search(s) for r in rx)  # noqa: E731
    walk = ("%mla_decode.7 = bf16[128,128,512]{2,1,0:T(8,128)(2,1)} "
            "custom-call(%a)")
    g = "%moe_gmm.12 = bf16[1552,2048]{1,0:T(8,128)(2,1)} custom-call(%b)"
    assert hit(mla, walk) and not hit(mla, g)
    assert hit(gmm, g) and not hit(gmm, walk)
    for other in ("%closed_call.3 = bf16[128,2048]{1,0} custom-call(%y)",
                  "%mla_absorb.3 = bf16[128,128,512]{2,1,0} fusion(%q)",
                  "%mla_decode.9 = bf16[128,128,640]{2,1,0} fusion(%q)"):
        assert not hit(mla, other) and not hit(gmm, other)
