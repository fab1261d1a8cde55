"""Device time of one jitted program's executions in the trace,
averaged over the chips. `program` says how to know the program in the
trace today (see `trace_reduce.executions`). per="step": total time
over the token steps made (executions x the scan's chunk), in ms;
per="execution": the median execution, in ms."""

import numpy as np

from benchmark import trace_reduce


def read(cap, *, program, per="execution"):
    if cap.trace is None:
        return None
    vals = []
    for dev in cap.trace.devices:
        durs = [b - a for a, b in trace_reduce.whole_executions(
            cap.trace, dev, program)]
        if not durs:
            continue
        if per == "step":
            vals.append(1e3 * sum(durs) / (len(durs) * cap.chunk))
        else:
            vals.append(1e3 * float(np.median(durs)))
    return float(np.mean(vals)) if vals else None
