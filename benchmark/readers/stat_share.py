"""Sum of some of the program's counters or gauges over the sum of
others, in percent. over="window": each as its delta over the window
(counters); over="close": each as it reads at the window's close
(gauges). None where the program has none of them (a program older than
the series) or the denominators sum to zero."""


def read(cap, *, numerators, denominators, over="window"):
    keys = list(numerators) + list(denominators)
    if any(k not in cap.stats1 for k in keys):
        return None
    base = cap.stats0 if over == "window" else {}
    val = lambda k: cap.stats1[k] - base.get(k, 0)  # noqa: E731
    den = sum(val(k) for k in denominators)
    return 100.0 * sum(val(k) for k in numerators) / den if den else None
