"""The control of the served-token comparison, at a size a test run can
hold: the reference put in the program's place and computed in the
nearest precision below the one the configuration states (fp8 for
bfloat16) has to come out as not correct, while tokens decoded greedily
in the stated arithmetic pass. The chip readings at the cells' own
sizes are in PERF.md; the limit tested here is the tiny cell's."""

import json
import os

import numpy as np

from benchmark.reference import qwen3

_TD = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "testdata")


def _tiny_bf16():
    with open(os.path.join(_TD, "tiny-qwen3.json")) as f:
        cfg = json.load(f)
    cfg["torch_dtype"] = "bfloat16"
    return cfg


def _greedy(cfg, seed, prompt, n, pad=32):
    """Greedy tokens of the reference itself, in the stated arithmetic.
    The sequence is padded to one length (attention is causal, so what
    follows a position cannot reach it): one compiled shape."""
    seq = list(prompt)
    for _ in range(n):
        ids = seq + [0] * (pad - len(seq))
        seq.append(int(np.argmax(np.asarray(
            qwen3.all_logits(cfg, seed, ids)[len(seq) - 1]))))
    return seq


def test_control_fails_and_sound_tokens_pass():
    cfg = _tiny_bf16()
    limit = 1e-3                       # limits/tiny.selfcheck-closed.json
    worst_sound, worst_control = 0.0, []
    for seed in (3, 2**31 + 4, 5):
        rng = np.random.default_rng(seed)
        prompts = [rng.integers(0, cfg["vocab_size"], size=16).tolist()
                   for _ in range(3)]
        seqs = [_greedy(cfg, seed, p, 12) for p in prompts]
        g = qwen3.served_token_gaps(cfg, seed, seqs, [16] * 3,
                                    precisions=("f32", "fp8"), pad_to=32)
        worst_sound = max(worst_sound,
                          max(float(x.max()) for x in g["f32"]))
        worst_control.append(max(float(x.max()) for x in g["fp8"]))
    assert worst_sound <= limit
    assert min(worst_control) > 3 * limit, worst_control


def test_wrong_token_reads_a_wide_gap():
    cfg = _tiny_bf16()
    rng = np.random.default_rng(11)
    seq = _greedy(cfg, 11, rng.integers(0, 512, size=16).tolist(), 8)
    seq[20] = (seq[20] + 1) % cfg["vocab_size"]
    g = qwen3.served_token_gaps(cfg, 11, [seq], [16], pad_to=32)
    assert float(g["f32"][0][4]) > 1e-3
