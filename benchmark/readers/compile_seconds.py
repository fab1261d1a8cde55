"""What jax traced, lowered and compiled, from the program's own
compile accounting: the counters `program_compile_s{program=<role>,
stage=<stage>}` (seconds) and `program_compile_n{...}` (events) that
`stats()` carries per role of an engine program (`eager`: whatever is
dispatched outside one) and per stage (`trace`, `lower`, `backend`:
the compiler or the persistent cache's load, whichever ran;
`cache_load`: that load alone, inside `backend`).

`value`: "seconds", "ms" or "count" sum the matching series;
"ratio_pct" is 100 x the events of `stages` over the events of `over`.
`roles` lists the roles to sum, `all_but_roles` the ones to leave out;
neither: every role. `at`: "setup" reads the totals before the first
timed request (`stats0`), "window" their growth over the window: 0
where nothing compiled. Nothing where the program keeps no such
counters (a program older than them), or a ratio's denominator is 0.
What each role gave goes to standard error beside the sum: a window
that reads above 0 names the program that compiled there.
"""

import re
import sys

_KEY = re.compile(
    r"^program_compile_(s|n)\{program=([^,}]+),stage=([^,}]+)\}$")


def _by_role(stats, what, stages, roles, all_but_roles):
    """{role: sum of its matching series} in one stats() snapshot, or
    None where it holds no series of the accounting at all."""
    out, found = {}, False
    for key, v in stats.items():
        m = _KEY.match(key)
        if not m:
            continue
        found = True
        w, role, stage = m.groups()
        if (w == what and stage in stages
                and (roles is None or role in roles)
                and role not in (all_but_roles or ())):
            out[role] = out.get(role, 0.0) + v
    return out if found else None


def read(cap, *, value, stages, at, over=None, roles=None,
         all_but_roles=None):
    if at not in ("setup", "window"):
        raise ValueError(f"at={at!r}: 'setup' or 'window'")
    if value not in ("seconds", "ms", "count", "ratio_pct"):
        raise ValueError(f"value={value!r}")

    def total(what, which):
        by = _by_role(cap.stats0, what, which, roles, all_but_roles)
        if at == "window" and by is not None:
            b1 = _by_role(cap.stats1, what, which, roles, all_but_roles)
            by = None if b1 is None else {
                r: v - by.get(r, 0.0) for r, v in b1.items()}
        if by is None:
            return None
        print(f"compile_seconds: {at} {'n' if what == 'n' else 's'} of "
              f"{'+'.join(which)} by role "
              f"{ {r: v for r, v in sorted(by.items()) if v} }",
              file=sys.stderr, flush=True)
        return sum(by.values())

    if value == "ratio_pct":
        num, den = total("n", stages), total("n", over)
        return 100.0 * num / den if den else None
    t = total("n" if value == "count" else "s", stages)
    if t is None:
        return None
    return 1e3 * t if value == "ms" else t
