"""The falcon_h1 work functions against hand counts at the published
widths (every expected number is worked out here from the
configuration's file and its byte table, not taken from the
function), and the new cell's data files against the readers they
name."""

import json
import os

import numpy as np
import pytest

from benchmark import spec
from benchmark.work import falcon_h1_attn, falcon_h1_ssd, falcon_h1_step

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "falcon-h1-34b-pp18.longreply-saturated"
ATTN = 31457280            # 2 x 5120x2560 + 2 x 5120x512
MAMBA = 68321280           # 5120 x 9,248 + 4096 x 5120
MLP = 330301440            # 3 x 5120 x 21504
HEAD = 261120 * 5120
STATE = 32 * 256 * 128     # a slot and layer's SSD state elements
CONV = 3 * 5120            # its conv rows


@pytest.fixture(scope="module")
def m():
    return spec.Benchmark(_ROOT).config("falcon-h1-34b-pp18")


def test_parameters_by_the_byte_table(m):
    assert falcon_h1_step.attn_params(m) == ATTN
    assert falcon_h1_step.mamba_params(m) == MAMBA
    assert 5120 * (2 * 4096 + 2 * 2 * 256 + 32) == 5120 * 9248
    assert falcon_h1_step.mlp_params(m) == MLP
    assert falcon_h1_step.conv_channels(m) == 5120
    # the table's 4 layers of 430.1M (the few norms and the conv aside)
    # and the embedding and head of 2,673,868,800
    assert 4 * (ATTN + MAMBA + MLP) == 1720320000
    assert 2 * HEAD == 2673868800
    assert falcon_h1_step.weight_bytes(m) == 2 * (4 * (ATTN + MAMBA + MLP)
                                                  + HEAD) == 6114508800
    # 4 MiB of SSD state a slot and layer; the table's 1,073,741,824 and
    # 15,728,640 B at 64 slots
    assert 4 * STATE == 4 * 2 ** 20
    assert 64 * falcon_h1_step.state_bytes_per_slot(m) == \
        1073741824 + 15728640


def test_decode_step_at_the_cells_shape(m):
    """64 slots: the state read and written is 2.15 GB (and 0.03 GB of
    conv rows), the pages 0.54 GB at a context of 1,024 and 1.94 GB at
    3,700, the floor 10.8-12.5 ms at 819 GB/s."""
    assert falcon_h1_step.kv_row_bytes(m) == 2048
    assert falcon_h1_step.attn_flops_per_key(m) == 10240   # 4 x 20 x 128
    w = falcon_h1_step.weight_bytes(m)
    for c, pages in ((1024, 0.537e9), (3700, 1.94e9)):
        b = falcon_h1_step.decode_token_bytes(m, np.full((64,), c))
        state = 2 * 64 * 4 * 4 * (STATE + CONV)
        assert b == 64 * c * 4 * 2048 + state
        assert abs((b - state) - pages) < 0.005e9
        assert abs(2 * 64 * 4 * 4 * STATE - 2.147e9) < 0.001e9
        floor = 1e3 * (w + b) / 819e9
        assert (10.7 < floor < 10.9) if c == 1024 else (12.4 < floor < 12.6)


def test_window_flops_by_hand(m):
    lay = falcon_h1_step.layer_token_flops(m)
    assert lay == 2 * (ATTN + MAMBA + MLP) + 2 * 4 * 5120 + 5 * STATE
    got = falcon_h1_step.window_flops(m, prompt_lens=[1024],
                                      contexts=[1024, 1025])
    n = 1024
    want = (4 * lay * n - 2 * MLP * (n - 1)
            + 10240 * 4 * (n * (n + 1) // 2)
            + 2 * (4 * lay + 2 * HEAD) + 10240 * 4 * (1024 + 1025))
    assert got == pytest.approx(want, rel=1e-12)


def test_kernel_work_per_step(m):
    kw = dict(steps=4.0, rows_per_step=64.0, kv_tokens_per_step=64 * 3000.0)
    ssd = falcon_h1_ssd.work(m, 1, **kw)
    rows = 4 * (2 * 4096 + 2 * 512 + 32)
    assert ssd["hbm_bytes"] == 4 * 4 * 64 * (8 * STATE + rows)
    assert ssd["flops"] == 4 * 4 * 5 * STATE * 64
    # bound by the state's bytes: ~0.5 GB a step and layer at 64 slots
    assert ssd["flops"] / ssd["hbm_bytes"] < 1.0
    at = falcon_h1_attn.work(m, 1, **kw)
    assert at["hbm_bytes"] == 4 * 4 * (2048 * 64 * 3000
                                       + 2 * 20 * 128 * 2 * 64)
    assert at["flops"] == 4 * 4 * 10240 * 64 * 3000
    scan = falcon_h1_ssd.scan_work(m, 1024)
    per_chunk = (2 * 2 * 128 * 128 * 256
                 + 32 * (2 * 128 * 128 * 128 + 4 * 128 * 256 * 128))
    assert scan["flops"] == 4 * 8 * per_chunk
    assert scan["hbm_bytes"] == 4 * (1024 * rows + 4 * STATE)


def test_cell_and_metrics_are_wired():
    b = spec.Benchmark(_ROOT)
    wl = b.workload(CELL)
    assert (wl["config"], wl["traffic"], wl["chips"]) == (
        "falcon-h1-34b-pp18", "longreply-saturated", 1)
    mix, cfg = b.traffic(wl["traffic"]), b.config(wl["config"])
    batch = cfg["server"]["batch"]
    assert batch in (64, 48)            # 48 where 64 would not fit
    assert mix["clients"] == mix["deck"] == batch * 3 // 2
    assert mix["prompt_len"]["values"] == [1024]
    assert (mix["output_len"]["lo"], mix["output_len"]["hi"]) == (3200, 3500)
    assert 1024 + 3500 <= cfg["engine"]["max_seq"] - 8
    names = [x["name"] for x in b.metrics_for(CELL, trace=True)]
    assert len(names) == 10 and all(n.endswith(".reply") for n in names)
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


# the published config.json of tiiuae/Falcon-H1-34B-Instruct, the keys
# that say something about its shape
PUBLISHED = {
    "attention_bias": False,
    "attention_in_multiplier": 1,
    "attention_out_multiplier": 0.0375,
    "attn_layer_indices": None,
    "embedding_multiplier": 5.656854249492381,
    "head_dim": 128,
    "hidden_act": "silu",
    "hidden_size": 5120,
    "intermediate_size": 21504,
    "key_multiplier": 0.011048543456039804,
    "lm_head_multiplier": 0.0078125,
    "mamba_chunk_size": 128,
    "mamba_conv_bias": True,
    "mamba_d_conv": 4,
    "mamba_d_head": 128,
    "mamba_d_ssm": 4096,
    "mamba_d_state": 256,
    "mamba_expand": 2,
    "mamba_n_groups": 2,
    "mamba_n_heads": 32,
    "mamba_norm_before_gate": False,
    "mamba_proj_bias": False,
    "mamba_rms_norm": True,
    "mamba_use_mlp": True,
    "max_position_embeddings": 262144,
    "mlp_bias": False,
    "mlp_expansion_factor": 8,
    "mlp_multipliers": [
        0.1767766952966369,
        0.011160714285714284
    ],
    "model_type": "falcon_h1",
    "num_attention_heads": 20,
    "num_hidden_layers": 72,
    "num_key_value_heads": 4,
    "num_logits_to_keep": 1,
    "projectors_bias": False,
    "rms_norm_eps": 1e-05,
    "rope_scaling": None,
    "rope_theta": 100000000000,
    "ssm_in_multiplier": 0.25,
    "ssm_multipliers": [
        0.3535533905932738,
        0.25,
        0.1767766952966369,
        0.5,
        0.3535533905932738
    ],
    "ssm_out_multiplier": 0.08838834764831845,
    "tie_word_embeddings": False,
    "vocab_size": 261120
}


def test_the_configuration_is_the_published_one_but_for_the_cut(m):
    """Every published key is in the file under the same name with the
    same value, but for the one in `reduced`."""
    assert m["source"] == ("https://huggingface.co/tiiuae/"
                           "Falcon-H1-34B-Instruct/blob/main/config.json")
    differ = sorted(k for k, v in PUBLISHED.items() if m.get(k) != v)
    assert differ == m["reduced"] == ["num_hidden_layers"]
    assert m["published"] == {"num_hidden_layers": 72}
    assert m["num_hidden_layers"] == 4
