"""Sum of some of the program's gauges over another, at the window's
close, in percent. None where the program has no such gauge (a program
older than the gauges) or the denominator reads zero."""


def read(cap, *, numerators, denominator):
    den = cap.stats1.get(denominator)
    if not den or any(k not in cap.stats1 for k in numerators):
        return None
    return 100.0 * sum(cap.stats1[k] for k in numerators) / den
