"""`tdcheck` — static analysis for the Pallas kernels and the serving
hot loop (ISSUE 15).

The reference Triton-distributed system's correctness rests on
hand-maintained protocols (one-sided signal set/wait pairing,
symmetric-buffer aliasing, barrier placement — SURVEY.md §2.3); this
TPU rebuild grew the same classes of invariant: paged-table write
exclusivity and CoW-on-refcount>1 discipline (models/prefix_cache.py),
per-shard page-id partitioning (kernels/paged_kv.PageAllocator),
zero-host-transfer poll loops (models/scheduler.py). The bitwise
differential suites catch a violation AFTER it corrupts a stream;
tdcheck makes the invariants statically checkable over every
registered kernel (kernels.kernel_registry) and every jitted slot
program (models.engine._jit_programs), BEFORE a tick runs.

Checkers (one module each):

- contracts  : walks the jaxpr of every registered kernel, extracts
               each pallas_call's grid/BlockSpecs/dtypes, estimates the
               per-grid-step VMEM footprint, flags over-budget kernels,
               non-divisible block shapes, and missing
               input_output_aliases on registered in-place kernels.
- races      : proves paged-KV write exclusivity — symbolically on the
               tick jaxpr (every pool write's indices must derive from
               the page table; pool operands of a pallas_call must not
               alias outputs undeclared) and on live scheduler state
               (no two slots write one physical page; no write to a
               refcount>1 page outside the CoW boundary), plus a
               shadow-page dynamic mode diffing pool bytes around a
               real tick under interpret.
- protocol   : builds the per-device signal graph of the one-sided
               kernels from dl.comm_trace() events and rejects
               unmatched set/wait pairs, wait-before-set orderings and
               barrier-elision hazards.
- hotloop    : hashes the jaxprs of the engine's _jit_programs set
               (double-trace determinism = no recompile-key churn
               between polls; lru identity = one program set
               process-wide) and fails on any host transfer
               (callback/infeed/outfeed) inside a decode-tick program.
- deadcode   : AST lint over the package — unused imports, unreachable
               fallback branches, shadowed names.

CLI: ``python -m triton_dist_tpu.analysis [checkers...]`` — exits
non-zero on any error finding; ``tools/tdcheck.sh`` is the CI smoke.
Every diagnostic carries a file:line. To ADD a checker: emit
`Finding`s, return a `Report`, register the runner in __main__.py
(ROADMAP standing note).
"""

from __future__ import annotations

import dataclasses
from typing import List


@dataclasses.dataclass
class Finding:
    """One diagnostic: which checker fired, where (file:line), on what
    (kernel/program/module name), and why."""

    checker: str
    severity: str            # "error" | "warning"
    where: str               # file:line (best effort, never empty)
    subject: str             # kernel / program / module name
    message: str

    def format(self) -> str:
        return (f"[{self.checker}] {self.severity.upper()} "
                f"{self.subject} @ {self.where}: {self.message}")


@dataclasses.dataclass
class Report:
    """A checker run's findings + the subjects it actually covered
    (coverage is part of the contract: an empty report over zero
    kernels is a broken scan, not a clean tree)."""

    checker: str
    findings: List[Finding] = dataclasses.field(default_factory=list)
    covered: List[str] = dataclasses.field(default_factory=list)

    def add(self, severity: str, where: str, subject: str,
            message: str) -> None:
        self.findings.append(Finding(self.checker, severity, where,
                                     subject, message))

    @property
    def errors(self) -> List[Finding]:
        return [f for f in self.findings if f.severity == "error"]

    def merge(self, other: "Report") -> "Report":
        self.findings.extend(other.findings)
        self.covered.extend(other.covered)
        return self

    def format(self) -> str:
        lines = [f.format() for f in self.findings]
        lines.append(f"[{self.checker}] covered {len(self.covered)} "
                     f"subject(s), {len(self.errors)} error(s), "
                     f"{len(self.findings) - len(self.errors)} "
                     f"warning(s)")
        return "\n".join(lines)


def iter_jaxprs(jaxpr):
    """Yield every (sub)jaxpr reachable from `jaxpr` (pjit/scan/while/
    cond/shard_map/custom_* bodies), outermost first."""
    import jax.extend.core as jc
    seen = set()
    stack = [jaxpr]
    while stack:
        jx = stack.pop()
        if id(jx) in seen:
            continue
        seen.add(id(jx))
        yield jx
        for eqn in jx.eqns:
            for v in eqn.params.values():
                vs = v if isinstance(v, (list, tuple)) else [v]
                for vv in vs:
                    if isinstance(vv, jc.ClosedJaxpr):
                        stack.append(vv.jaxpr)
                    elif isinstance(vv, jc.Jaxpr):
                        stack.append(vv)


def iter_eqns(jaxpr, primitive: str = None):
    """Yield every eqn in the nested jaxpr, optionally filtered by
    primitive name. pallas_call kernel bodies are descended too."""
    for jx in iter_jaxprs(jaxpr):
        for eqn in jx.eqns:
            if primitive is None or eqn.primitive.name == primitive:
                yield eqn


def _kernel_debug_info(eqn):
    """debug_info of a pallas_call eqn's kernel body (None otherwise)."""
    body = eqn.params.get("jaxpr") if eqn.primitive.name == "pallas_call" \
        else None
    return getattr(body, "debug_info", None)


def pallas_kernel_name(eqn) -> str:
    """A pallas_call eqn's `name=`, else its kernel function's name."""
    return eqn.params["name"] or _kernel_debug_info(eqn).func_name


def eqn_src(eqn) -> str:
    """Best-effort file:line of an eqn (the user frame of its source
    info; pallas_call eqns prefer their kernel's definition site)."""
    di = _kernel_debug_info(eqn)
    if di is not None and di.func_filename:
        return f"{di.func_filename}:{di.func_lineno}"
    try:
        from jax._src import source_info_util as siu
        fr = siu.user_frame(eqn.source_info)
        if fr is not None:
            return f"{fr.file_name}:{fr.start_line}"
    except Exception:
        pass
    return "<unknown>"
