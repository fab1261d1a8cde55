"""A percentile of a per-request latency on the client's clock, over
every request due in the window. A request that failed or never
finished counts as the worst value: the time until the run gave up."""

import numpy as np


def _value(r, what, cap):
    worst = 1e3 * (cap.drain_end - r.due)
    if what == "lateness":
        return 1e3 * (r.sent - r.due)
    if not r.ok or r.first is None:
        return worst
    if what == "ttft":
        return 1e3 * (r.first - r.due)
    if what == "tpot":
        after = len(r.tokens) - r.n_first
        if after <= 0:
            return None
        return 1e3 * (r.last - r.first) / after
    raise ValueError(what)


def read(cap, *, what, q=95):
    vals = [v for v in (_value(r, what, cap) for r in cap.records)
            if v is not None]
    return float(np.percentile(vals, q)) if vals else None
