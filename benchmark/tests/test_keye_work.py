"""The keye work functions against hand counts at the published widths
(every expected number is worked out here from the configuration's file
and ISSUE 39's tables, not taken from the function), and the new cell's
data files against the readers they name."""

import json
import os

import numpy as np
import pytest

from benchmark import spec
from benchmark.work import (keye_moe, keye_sa_attend, keye_sa_index,
                            keye_step)

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "keye-vl2-30b-a3b-ep8.longdoc-saturated"
ATTN = 18874368            # 2048x4096 + 2 x 2048x512 + 4096x2048
INDEXER = 2260992          # 2048x1024 + 2048x64 + 2048x16
ROUTER = 262144            # 2048 x 128
EXPERT = 4718592           # 3 x 2048 x 768
HEAD = 151936 * 2048


@pytest.fixture(scope="module")
def m():
    return spec.Benchmark(_ROOT).config("keye-vl2-30b-a3b-ep8")


def test_parameters_by_the_issues_table(m):
    assert keye_step.attn_params(m) == ATTN
    assert keye_step.indexer_params(m) == INDEXER
    assert keye_step.router_params(m) == ROUTER
    assert keye_step.expert_params(m) == EXPERT
    assert keye_step.layer_params(m) == 96894976 \
        == ATTN + INDEXER + ROUTER + 16 * EXPERT
    assert 2 * keye_step.layer_params(m) == 193789952
    # the table's 1,244,659,712 B hold the embedding too, which a decode
    # step does not read (a row a token)
    assert 2 * 2 * HEAD == 1244659712
    # a step's 32 pairs reach 13.97 of the 16 held experts
    touched = 16 * (1 - (15 / 16) ** 32)
    assert 13.9 < touched < 14.0
    assert keye_step.experts_touched(m, 32) == pytest.approx(touched)
    assert keye_step.experts_touched(m, 16384) == pytest.approx(16.0)
    assert keye_step.weight_bytes(m) == pytest.approx(
        2 * (6 * (ATTN + INDEXER + ROUTER + touched * EXPERT) + HEAD))


def test_routed_share_and_attention_counts(m):
    assert keye_step.held_pairs_per_token(m) == 1.0         # 8 x 16/128
    assert keye_step.index_row_bytes(m) == 128
    assert keye_step.kv_row_bytes(m) == 2048
    assert keye_step.index_flops_per_key(m) == 2048         # 2 x 16 x 64
    assert keye_step.attn_flops_per_key(m) == 16384         # 4 x 32 x 128
    np.testing.assert_array_equal(
        keye_step.attended(m, [5, 2048, 18000]), [5, 2048, 2048])


def test_decode_step_at_the_issues_shape(m):
    """32 slots at a context of 18,000: the issue's table row by row."""
    ctx = np.full((32,), 18000)
    w = keye_step.weight_bytes(m)
    # the issue's 1.163 GB of layers with all 16 experts touched is
    # 1.048 with the 13.97 a step's pairs reach
    assert abs(w - (1.048e9 + 0.622e9)) < 0.001e9
    b = keye_step.decode_token_bytes(m, ctx)
    index, rows = 6 * 32 * 18000 * 128, 6 * 32 * 2048 * 2048
    assert b == index + rows
    assert abs(index - 0.44e9) < 0.005e9 and abs(rows - 0.81e9) < 0.01e9
    assert 3.5 < 1e3 * (w + b) / 819e9 < 3.6             # the step's floor
    whole = 6 * 32 * 18000 * 2048
    assert abs(whole - 7.08e9) < 0.01e9                  # if it read it all


def test_window_flops_by_hand(m):
    tok = keye_step.token_flops(m)
    assert tok == 6 * 2 * (ATTN + INDEXER + ROUTER + 1.0 * EXPERT)
    got = keye_step.window_flops(m, prompt_lens=[16384],
                                 contexts=[16384, 16385])
    n, k = 16384, 2048
    sel = k * (k + 1) // 2 + (n - k) * k
    want = (tok * n + 6 * (2048 * (n * (n + 1) // 2) + 16384 * sel)
            + 2 * (tok + 2 * HEAD)
            + 6 * (2048 * (16384 + 16385) + 16384 * 2 * k))
    assert got == pytest.approx(want, rel=1e-12)
    # a short prompt attends everything it scores
    short = keye_step.window_flops(m, prompt_lens=[100], contexts=[])
    assert short == pytest.approx(
        tok * 100 + 6 * (2048 + 16384) * (100 * 101 // 2), rel=1e-12)


def test_kernel_work_per_step(m):
    kw = dict(steps=4.0, rows_per_step=32.0,
              kv_tokens_per_step=32 * 18000.0)
    ix = keye_sa_index.work(m, 1, **kw)
    assert ix["hbm_bytes"] == 4 * 6 * (128 * 32 * 18000
                                       + (16 * 64 + 16) * 2 * 32)
    assert ix["flops"] == 4 * 6 * 2048 * 32 * 18000
    at = keye_sa_attend.work(m, 1, **kw)
    assert at["hbm_bytes"] == 4 * 6 * (2048 * 32 * 2048
                                       + 2 * 32 * 128 * 2 * 32)
    assert at["flops"] == 4 * 6 * 16384 * 32 * 2048
    # a context under topk attends all of itself
    few = keye_sa_attend.work(m, 1, steps=1.0, rows_per_step=2.0,
                              kv_tokens_per_step=2 * 100.0)
    assert few["flops"] == 6 * 16384 * 200
    moe = keye_moe.work(m, 1, steps=4.0, rows_per_step=32.0)
    assert moe["hbm_bytes"] == pytest.approx(4 * 6 * (
        16 * (1 - (15 / 16) ** 32) * EXPERT * 2
        + 2 * (2048 + 768) * 2 * 32))
    assert moe["flops"] == 4 * 6 * 2 * EXPERT * 32


def test_cell_and_metrics_are_wired():
    b = spec.Benchmark(_ROOT)
    wl = b.workload(CELL)
    assert (wl["config"], wl["traffic"], wl["chips"]) == (
        "keye-vl2-30b-a3b-ep8", "longdoc-saturated", 1)
    mix = b.traffic(wl["traffic"])
    assert mix["clients"] == 48 and mix["prompt_len"]["values"] == [16384]
    assert (mix["output_len"]["lo"], mix["output_len"]["hi"]) == (2048, 4000)
    names = [x["name"] for x in b.metrics_for(CELL, trace=True)]
    assert len(names) == 14 and all(n.endswith(".longdoc") for n in names)
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
    assert "max_gap" not in b.limits(CELL) or b.limits(CELL)["max_gap"] > 0
