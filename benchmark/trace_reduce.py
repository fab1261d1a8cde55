"""From a profiler trace (`.xplane.pb`) to numbers: per-device busy
intervals, device time per name pattern, program executions, and the
longest idle gaps with what the host was doing in them.

Read with `jax.profiler.ProfileData` alone. On a TPU the trace has one
plane per chip, `/device:TPU:<n>`, whose line `XLA Ops` holds one event
per device operation (fusions, custom calls = Pallas kernels, copies)
and whose line `XLA Modules` holds one event per program execution,
named after the jitted function. Host threads are lines of the
`/host:CPU` plane; the benchmark's own `TraceAnnotation`s (names
starting `bench:`) land there on the same clock.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_MARK = "bench:"


@dataclasses.dataclass
class DeviceTrace:
    index: int
    ops: List[Tuple[str, float, float]]        # (name, start_s, dur_s)
    modules: List[Tuple[str, float, float]]


@dataclasses.dataclass
class Trace:
    devices: List[DeviceTrace]
    host_marks: List[Tuple[str, float, float]]  # bench: annotations
    start_s: float
    stop_s: float

    @property
    def window_s(self) -> float:
        return self.stop_s - self.start_s


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _events(line) -> List[Tuple[str, float, float]]:
    return [(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
            for e in line.events]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, marks = [], []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops, mods = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = sorted(_events(line), key=lambda e: e[1])
                elif line.name == MODULES_LINE:
                    mods = _events(line)
            devices.append(DeviceTrace(int(m.group(2)), ops, mods))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_MARK):
                        marks.append((e.name[len(HOST_MARK):],
                                      e.start_ns * 1e-9,
                                      e.duration_ns * 1e-9))
    devices.sort(key=lambda d: d.index)
    spans = [(s, s + d) for dev in devices
             for _, s, d in (dev.ops or dev.modules)]
    if not spans:
        raise ValueError(f"{path}: no device operation in the trace")
    start = min(s for s, _ in spans)
    stop = max(e for _, e in spans)
    return Trace(devices, sorted(marks, key=lambda m: m[1]), start, stop)


def busy_intervals(dev: DeviceTrace) -> List[Tuple[float, float]]:
    """Union of the intervals in which an operation ran on the device."""
    evs = sorted((s, s + d) for _, s, d in (dev.ops or dev.modules))
    out: List[List[float]] = []
    for a, b in evs:
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_seconds(dev: DeviceTrace) -> float:
    return sum(b - a for a, b in busy_intervals(dev))


def pattern_seconds(ops, patterns) -> Tuple[float, int]:
    """Summed device time and count of the operation events whose name
    matches any of the regular expressions."""
    rx = [re.compile(p) for p in patterns]
    hit = [d for n, _, d in ops if any(r.search(n) for r in rx)]
    return sum(hit), len(hit)


def ops_inside(dev: DeviceTrace, spans) -> list:
    """The operation events that start inside any of the (start, stop)
    spans: the operations of those program executions."""
    starts = [s for _, s, _ in dev.ops]
    out = []
    for a, b in spans:
        out.extend(dev.ops[bisect.bisect_left(starts, a):
                           bisect.bisect_left(starts, b)])
    return out


def executions(dev: DeviceTrace, program: dict) -> List[Tuple[float, float]]:
    """(start, stop) of the executions of one jitted program. `program`
    says how to know it in the trace today: `patterns` (regular
    expressions on the `XLA Modules` event's name) and, because the
    program's jitted partials all show as `jit__unknown(<hash>)`,
    optionally `has_op` / `lacks_op`: an operation the execution must /
    must not contain (the decode scan is the one with a `%while`)."""
    rx = [re.compile(p) for p in program["patterns"]]
    has = re.compile(program["has_op"]) if program.get("has_op") else None
    lacks = re.compile(program["lacks_op"]) if program.get("lacks_op") \
        else None
    out = []
    for n, s, d in dev.modules:
        if not any(r.search(n) for r in rx):
            continue
        if has or lacks:
            names = [o[0] for o in ops_inside(dev, [(s, s + d)])]
            if has and not any(has.search(x) for x in names):
                continue
            if lacks and any(lacks.search(x) for x in names):
                continue
        out.append((s, s + d))
    return out


def whole_executions(trace: Trace, dev: DeviceTrace, program: dict):
    """The executions that lie wholly inside the traced slice: one cut
    by either end has done an unknown part of its work (and has lost
    the operation that identifies it)."""
    return [(a, b) for a, b in executions(dev, program)
            if a > trace.start_s + 1e-4 and b < trace.stop_s - 1e-4]


_CONTAINERS = ("while", "conditional", "call")


def short_name(name: str) -> Tuple[str, str]:
    """(a short label, the opcode) of an operation event, whose name is
    the whole HLO instruction: `%closed_call.484 = bf16[256,2,128]{...}
    custom-call(...)` becomes (`closed_call custom-call bf16[256,2,128]`,
    `custom-call`). Numbered twins share a label."""
    m = re.match(r"^%?([^ =]+?)(?:\.\d+)? = (.*)$", name, re.S)
    if not m:
        return name[:80], ""
    base, rest = m.group(1), m.group(2)
    op = re.search(r"(?:^|[\s)])([a-z][a-z0-9_\-]*)\(", rest)
    opcode = op.group(1) if op else ""
    if not opcode and base in _CONTAINERS:
        opcode = base                 # its result type alone fills a name
    shape = re.match(r"^([a-z0-9]+\[[0-9,]*\])", rest)
    label = " ".join(x for x in (base, opcode,
                                 shape.group(1) if shape else "") if x)
    return label[:120], opcode


def top_ops(dev: DeviceTrace, k: int = 10) -> List[List]:
    """The k operation labels that took most device time. Operations
    that only contain others (a scan's `while`) are left out."""
    acc: Dict[str, float] = {}
    for n, _, d in dev.ops or dev.modules:
        label, opcode = short_name(n)
        if opcode in _CONTAINERS:
            continue
        acc[label] = acc.get(label, 0.0) + d
    return [[n, t] for n, t in sorted(acc.items(),
                                      key=lambda kv: -kv[1])[:k]]


def _mark_at(marks, t: float) -> Optional[str]:
    """The innermost (shortest) host annotation covering instant t."""
    best = None
    for n, s, d in marks:
        if s > t:
            break
        if s <= t <= s + d and (best is None or d < best[1]):
            best = (n, d)
    return None if best is None else best[0]


def idle_gaps(trace: Trace, dev: DeviceTrace, k: int = 10) -> List[List]:
    """Idle time of the device inside the window, summed by what the
    host was doing at each gap's middle (a `bench:` annotation's name, or
    `unattributed`), the k largest sums."""
    iv = busy_intervals(dev)
    edges = [trace.start_s] + [x for ab in iv for x in ab] + [trace.stop_s]
    acc: Dict[str, float] = {}
    for i in range(0, len(edges), 2):
        a, b = edges[i], edges[i + 1]
        if b > a:
            name = _mark_at(trace.host_marks, 0.5 * (a + b)) \
                or "unattributed"
            acc[name] = acc.get(name, 0.0) + (b - a)
    return [[n, t] for n, t in sorted(acc.items(),
                                      key=lambda kv: -kv[1])[:k]]


def summary(trace: Trace) -> dict:
    """Names by line, for reading a trace by hand."""
    out = {"window_s": trace.window_s, "devices": []}
    for d in trace.devices:
        mods: Dict[str, List[float]] = {}
        for n, _, dur in d.modules:
            mods.setdefault(n, []).append(dur)
        out["devices"].append({
            "index": d.index, "busy_s": busy_seconds(d),
            "n_ops": len(d.ops), "top_ops": top_ops(d, 40),
            "custom_calls": sorted({n[:400] for n, _, _ in d.ops
                                    if "custom-call(" in n})[:60],
            "modules": {n: [len(v), sum(v)] for n, v in mods.items()}})
    out["host_marks"] = sorted({m[0] for m in trace.host_marks})
    return out
