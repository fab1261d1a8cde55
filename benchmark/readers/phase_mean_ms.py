"""Mean milliseconds of some phases of the program's host path per
event of one of its counters: delta(summed `host_phase_s`) / delta of
the counter `per`, over the window (`phases` / `all_but` as in
`phase_share`). The exits of every phase in the window go to standard
error beside it: how many of the serve loop's iterations slept idle
says how far an idle server dilutes a mean per iteration."""

import sys

from benchmark.readers.phase_share import seconds


def read(cap, *, per, phases=None, all_but=None):
    secs = seconds(cap, phases, all_but)
    n = cap.stats1.get(per, 0) - cap.stats0.get(per, 0)
    if secs is None or n <= 0:
        return None
    tag = "host_phase_n{phase="
    exits = {k[len(tag):-1]: v - cap.stats0.get(k, 0)
             for k, v in cap.stats1.items() if k.startswith(tag)}
    print(f"phase_mean_ms: {secs!r} s over {n} {per}; phase exits in "
          f"the window {exits}", file=sys.stderr, flush=True)
    return 1e3 * secs / n
