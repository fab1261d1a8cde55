"""Share of the window in which the scheduler's thread was NOT blocked
on the device: 1 - delta(sum of device_wait_s_by_kind) / window."""


def read(cap):
    w0 = sum(cap.stats0.get("device_wait_s_by_kind", {}).values())
    w1 = sum(cap.stats1.get("device_wait_s_by_kind", {}).values())
    if w1 <= w0:
        return None
    return 100.0 * (1.0 - (w1 - w0) / cap.seconds)
