"""What the per-kind work functions need of a window and the capture's
sums do not keep: the prompts admitted in it, and for every output token
received in it the context its step attended."""

import numpy as np


def read(cap):
    """(prompt lengths of the requests whose first token came inside the
    window, contexts of the output tokens received inside it)."""
    prompts, ctx = [], []
    for r in cap.records:
        n = len(r.prompt)
        if r.first is not None and cap.t0 <= r.first <= cap.t1:
            prompts.append(n)
        j = 0
        for t, k in r.token_times:
            if cap.t0 <= t <= cap.t1:
                ctx.append(n + j + np.arange(k))
            j += k
    return (np.asarray(prompts, np.int64),
            np.concatenate(ctx) if ctx else np.zeros((0,), np.int64))
