"""1 - (union of device-operation intervals) / traced slice, on the
fullest-loaded chip."""

from benchmark import trace_reduce


def read(cap):
    if cap.trace is None:
        return None
    busy = max(trace_reduce.busy_seconds(d) for d in cap.trace.devices)
    return 100.0 * (1.0 - busy / cap.trace.window_s)
