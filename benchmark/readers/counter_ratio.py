"""delta(numerator) / delta(denominator) of two of the program's
counters over the window, in percent."""


def read(cap, *, numerator, denominator):
    d = lambda k: cap.stats1.get(k, 0) - cap.stats0.get(k, 0)  # noqa: E731
    den = d(denominator)
    return 100.0 * d(numerator) / den if den else None
