"""The afmoe work functions against hand counts at the published widths
(every expected number is worked out here from the configuration's file
and ISSUE 43's table, not taken from the function), and the new cell's
data files against the readers they name."""

import json
import os

import numpy as np
import pytest

from benchmark import spec
from benchmark.work import (afmoe_attn_full, afmoe_attn_swa, afmoe_moe,
                            afmoe_step)

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "trinity-mini-ep8.agent-saturated"
ATTN = 27262976            # 3 x 2048x4096 + 2 x 2048x512
DENSE = 37748736           # 3 x 2048 x 6144
EXPERT = 6291456           # 3 x 2048 x 1024
ROUTER = 262144            # 2048 x 128
HEAD = 200192 * 2048


@pytest.fixture(scope="module")
def m():
    return spec.Benchmark(_ROOT).config("trinity-mini-ep8")


def test_parameters_by_the_issues_table(m):
    assert afmoe_step.attn_params(m) == ATTN and 2 * ATTN == 54525952
    assert afmoe_step.dense_params(m) == DENSE
    assert afmoe_step.expert_params(m) == EXPERT == \
        afmoe_step.shared_params(m)
    assert afmoe_step.router_params(m) == ROUTER
    assert afmoe_step.kinds(m) == {"swa": 6, "full": 2, "dense": 1,
                                   "moe": 7}
    # an expert layer 268,959,744 B, the dense one 130,023,424 B, all
    # eight 2,012,741,632 B: the issue's table
    assert 2 * (ATTN + EXPERT + ROUTER + 16 * EXPERT) == 268959744
    assert 2 * (ATTN + DENSE) == 130023424
    assert 2 * afmoe_step.stack_params(m, 16) == 2012741632
    assert 2 * 2 * HEAD == 1639972864
    # a step's 64 pairs reach 15.74 of the 16 held experts
    touched = 16 * (1 - (15 / 16) ** 64)
    assert 15.7 < touched < 15.8
    assert afmoe_step.experts_touched(m, 64) == pytest.approx(touched)
    assert afmoe_step.weight_bytes(m) == pytest.approx(2 * (
        8 * ATTN + DENSE + 7 * (EXPERT + ROUTER + touched * EXPERT)
        + HEAD))


def test_routed_share_and_attention_counts(m):
    assert afmoe_step.held_pairs_per_token(m) == 1.0        # 8 x 16/128
    assert afmoe_step.kv_row_bytes(m) == 2048
    assert afmoe_step.attn_flops_per_key(m) == 16384        # 4 x 32 x 128
    np.testing.assert_array_equal(
        afmoe_step.in_window(m, [5, 2048, 9000]), [5, 2048, 2048])


def test_decode_step_at_the_issues_shape(m):
    """64 slots at a context of 9,300: the issue's 'attention about 4.2
    GB of a step's ~7.9 GB', of which the two full layers take 57-62 %,
    and a floor of ~9.6 ms."""
    ctx = np.full((64,), 9300)
    w = afmoe_step.weight_bytes(m)
    assert abs(w - 2.81e9) < 0.01e9     # the table's 3.65 GB less the
    #                                     embedding, 0.26 experts a layer
    b = afmoe_step.decode_token_bytes(m, ctx)
    full, rings = 2 * 64 * 9300 * 2048, 6 * 64 * 2048 * 2048
    assert b == full + rings
    assert 0.57 < full / b < 0.62 and abs(b - 4.05e9) < 0.01e9
    assert 8.3 < 1e3 * (w + b) / 819e9 < 8.5           # the step's floor


def test_window_flops_by_hand(m):
    tok = afmoe_step.token_flops(m)
    assert tok == 2 * (8 * ATTN + DENSE + 7 * (EXPERT + ROUTER + EXPERT))
    got = afmoe_step.window_flops(m, prompt_lens=[8192],
                                  contexts=[8192, 8193])
    n, W = 8192, 2048
    win = W * (W + 1) // 2 + (n - W) * W
    want = (tok * n + 16384 * (2 * (n * (n + 1) // 2) + 6 * win)
            + 2 * (tok + 2 * HEAD)
            + 16384 * (2 * (8192 + 8193) + 6 * 2 * W))
    assert got == pytest.approx(want, rel=1e-12)
    # a prompt inside the window attends all of itself in every layer
    short = afmoe_step.window_flops(m, prompt_lens=[100], contexts=[])
    assert short == pytest.approx(
        tok * 100 + 8 * 16384 * (100 * 101 // 2), rel=1e-12)


def test_kernel_work_per_step(m):
    kw = dict(steps=4.0, rows_per_step=64.0,
              kv_tokens_per_step=64 * 9300.0)
    qo = 2 * 32 * 128 * 2 * 64
    sw = afmoe_attn_swa.work(m, 1, **kw)
    assert sw["hbm_bytes"] == 4 * 6 * (2048 * 64 * 2048 + qo)
    assert sw["flops"] == 4 * 6 * 16384 * 64 * 2048
    fu = afmoe_attn_full.work(m, 1, **kw)
    assert fu["hbm_bytes"] == 4 * 2 * (2048 * 64 * 9300 + qo)
    assert fu["flops"] == 4 * 2 * 16384 * 64 * 9300
    # a context inside the window: a ring walk reads all of it
    few = afmoe_attn_swa.work(m, 1, steps=1.0, rows_per_step=2.0,
                              kv_tokens_per_step=2 * 100.0)
    assert few["flops"] == 6 * 16384 * 200
    moe = afmoe_moe.work(m, 1, steps=4.0, rows_per_step=64.0)
    assert moe["hbm_bytes"] == pytest.approx(4 * 7 * (
        16 * (1 - (15 / 16) ** 64) * EXPERT * 2
        + 2 * (2048 + 1024) * 2 * 64))
    assert moe["flops"] == 4 * 7 * 2 * EXPERT * 64


def test_cell_and_metrics_are_wired():
    b = spec.Benchmark(_ROOT)
    wl = b.workload(CELL)
    assert (wl["config"], wl["traffic"], wl["chips"]) == (
        "trinity-mini-ep8", "agent-saturated", 1)
    mix, cfg = b.traffic(wl["traffic"]), b.config(wl["config"])
    batch = cfg["server"]["batch"]
    assert batch in (64, 48, 32)        # the issue's one adjustment
    assert mix["clients"] == mix["deck"] == batch * 3 // 2
    assert mix["prompt_len"]["values"] == [8192]
    assert (mix["output_len"]["lo"], mix["output_len"]["hi"]) == (3584, 4000)
    assert 8192 + 4000 <= cfg["engine"]["max_seq"] - 8
    names = [x["name"] for x in b.metrics_for(CELL, trace=True)]
    assert len(names) == 14 and all(n.endswith(".agent") for n in names)
    for n in names:
        with open(os.path.join(_ROOT, "benchmark", "metrics",
                               n + ".json")) as f:
            spec_ = json.load(f)
        assert os.path.exists(os.path.join(
            _ROOT, "benchmark", "readers", spec_["reader"] + ".py")), n
        work = spec_.get("args", {}).get("work")
        assert work is None or os.path.exists(os.path.join(
            _ROOT, "benchmark", "work", work + ".py")), n
    assert [x["name"] for x in b.metrics_for(CELL, trace=False)] == [
        "out_tokens_per_s", "setup_s"]
    lim = b.limits(CELL)
    assert lim["sample_requests"] >= 4 and 0 < lim["mean_gap"] < 1
    assert "max_gap" not in lim or lim["max_gap"] > 0


def test_the_configuration_is_the_catalogs_but_for_the_cut(m):
    """Every key the catalog's entry has is in the file under the same
    name with the same value, but for the three in `reduced`."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog on this machine")
    with open(path) as f:
        cat = [json.loads(line) for line in f if "Trinity-Mini" in line][0]
    assert m["source"] == cat["source_url"]
    differ = sorted(k for k, v in cat["config"].items() if m.get(k) != v)
    assert differ == sorted(m["reduced"]) == [
        "num_dense_layers", "num_experts", "num_hidden_layers"]
    assert m["published"] == {k: cat["config"][k] for k in m["reduced"]}


def test_stat_share_reads_sums_and_returns_nothing_without_the_series():
    from benchmark.readers import stat_share

    class Cap:
        stats0 = {"a": 10, "b": 30}
        stats1 = {"a": 30, "b": 110, "g": 5, "h": 15}

    assert stat_share.read(Cap, numerators=["a"],
                           denominators=["a", "b"]) == 20.0
    assert stat_share.read(Cap, numerators=["g"], denominators=["g", "h"],
                           over="close") == 25.0
    # a program older than the series: nothing, and no error
    assert stat_share.read(Cap, numerators=["x"],
                           denominators=["x", "a"]) is None


def test_slice_tokens_counts_what_arrived_inside_the_traced_slice():
    """A 40 s window is traced from second 10 to 14: of a stream with an
    8,192-token prompt whose messages of 4 tokens arrive every second
    from second 8.5 on, the four at 10.5 .. 13.5 count, each token at
    the context its step attended."""
    from benchmark.readers import work_roofline_slice as wrs

    class Rec:
        prompt = [0] * 8192
        token_times = [(100.0 + 8.5 + i, 4) for i in range(8)]

    class Cap:
        seconds, t0, records = 40.0, 100.0, [Rec]

    span, toks, keys = wrs.slice_tokens(Cap)
    assert (span, toks) == (4.0, 16)
    # messages 2..5 of the stream: tokens 8 .. 23 after the prompt
    assert keys == sum(8192 + j for j in range(8, 24))
    # without a trace the reader returns nothing and does not raise
    Cap.trace = Cap.peaks = None
    assert wrs.read(Cap, work="afmoe_attn_full", patterns=[],
                    program={}) is None
