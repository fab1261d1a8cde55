"""Share of the window that phases of the program's host path took, by
its own always-on totals (`stats()["host_phase_s"]`: self seconds per
phase of the serve loop and the scheduler, which partition the serve
thread's wall time): 100 x delta(summed seconds) / window, over the
whole window and with tracing off as well as on. `phases` lists the
phases to sum; `all_but` sums every phase the program has except the
listed. Nothing where the program keeps no such totals."""


def seconds(cap, phases=None, all_but=None):
    """Delta over the window of the summed self seconds, or None."""
    s0 = cap.stats0.get("host_phase_s")
    s1 = cap.stats1.get("host_phase_s")
    if not s0 or not s1:
        return None
    names = (list(phases) if phases is not None
             else [n for n in s1 if n not in all_but])
    if any(n not in s0 or n not in s1 for n in names):
        return None
    return sum(s1[n] - s0[n] for n in names)


def read(cap, *, phases=None, all_but=None):
    secs = seconds(cap, phases, all_but)
    if secs is None or not cap.seconds:
        return None
    return 100.0 * secs / cap.seconds
