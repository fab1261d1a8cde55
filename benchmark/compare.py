"""The comparison that decides `correct`, for a model that is served.

Once the window has closed and the program's state is freed, a sample of
the requests it finished (drawn from the seed, the longest among them)
goes through the configuration's plain reference: one full forward pass
over each prompt with its served tokens. For every served token the gap
by which its logit lies below the reference's best at that position is
read; greedy decoding in the stated precision keeps every gap small,
and a pass in a lower precision, a wrong mask, page or position does
not. The numbers compared, each beside its limit from
`limits/<workload>.json`:

  max_gap      the widest gap over all served tokens of the sample
  mean_gap     their mean (steadier than a maximum)
  unanswered   requests due in the window that never came back whole
"""

from __future__ import annotations

import importlib

import numpy as np


def pick_sample(done, seed: int, n: int):
    """`n` of the requests with served tokens (finished, or cut at the
    close of a closed loop), drawn from the seed, the longest first."""
    if not done:
        return []
    longest = max(done, key=lambda r: len(r.prompt) + len(r.tokens))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng(int(seed) ^ 0xC0FFEE)
    take = rng.permutation(len(rest))[:max(0, n - 1)]
    return [longest] + [rest[i] for i in sorted(take)]


def gaps_of(cfg, seed, sample, device, precisions=("f32",)):
    ref = importlib.import_module("benchmark.reference." + cfg["family"])
    seqs = [list(map(int, r.prompt)) + list(r.tokens) for r in sample]
    return ref.served_token_gaps(
        cfg, seed, seqs, [len(r.prompt) for r in sample],
        precisions=precisions, device=device)


def served_tokens(bench, wl, cfg, seed, served, unanswered: int, device,
                  *, control=()) -> dict:
    """`served`: the requests whose tokens can be checked; `unanswered`:
    how many requests due in the window never came back whole."""
    limits = bench.limits(wl["name"])
    sample = pick_sample(served, seed, int(limits["sample_requests"]))
    out = {}
    out["unanswered"] = {"value": unanswered, "limit": 0,
                         "ok": unanswered == 0}
    if not sample:
        out["max_gap"] = {"value": None, "limit": limits["max_gap"],
                          "ok": False}
        return out
    all_gaps = gaps_of(cfg, seed, sample, device,
                       precisions=("f32",) + tuple(control))
    gaps = np.concatenate(all_gaps["f32"])
    for prec in control:        # readings for a limit, never compared
        g = np.concatenate(all_gaps[prec])
        out["control_" + prec] = {
            "max_gap": float(g.max()), "mean_gap": float(g.mean()),
            "p99_gap": float(np.percentile(g, 99)),
            "share_at_best": float((g == 0).mean())}
    for name, val in (("max_gap", float(gaps.max())),
                      ("mean_gap", float(gaps.mean()))):
        if name in limits:
            out[name] = {"value": val, "limit": limits[name],
                         "ok": bool(np.isfinite(val)
                                    and val <= limits[name])}
    print(f"compared {gaps.size} served tokens of {len(sample)} requests; "
          f"gap mean {float(gaps.mean()):.6f} p50 "
          f"{float(np.median(gaps)):.6f} p99 "
          f"{float(np.percentile(gaps, 99)):.6f}; share at the reference's "
          f"best {float((gaps == 0).mean()):.4f}", flush=True)
    return out
