"""A percentile of the time between two lifecycle events of the traced
scheduler (`TokenServer(trace=True)`), over the requests whose first
event fell inside the window. The program's clock is its own monotonic
one; only differences are used."""

import numpy as np


def read(cap, *, start="queued", end="admitted", q=95):
    waits = []
    for r in cap.lifecycle.values():
        at = {}
        for ms, name, _ in r.get("events", []):
            at.setdefault(name, ms)
        if start in at and end in at:
            waits.append(at[end] - at[start])
    # the ring keeps the newest requests; the window's are the last ones
    waits = waits[-len(cap.records):] if cap.records else waits
    return float(np.percentile(waits, q)) if waits else None
