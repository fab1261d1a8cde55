"""Paged-KV race detector (tdcheck checker 2).

The paged serving stack's correctness rests on WRITE EXCLUSIVITY: in
one tick, no two slots may write the same physical page (a page
holds one slot's positions for all of its kv heads:
kernels/paged_kv.py append_slots), and no slot may write a
page whose refcount exceeds 1 — a shared page is radix-tree prefix KV,
writable only through the CoW boundary-copy path
(models/prefix_cache.py). A violation corrupts a DIFFERENT request's
stream, which the bitwise suites only catch after the fact. Three
complementary proofs:

1. **state check** (`check_state` / `check_scheduler`): over the live
   host-side state — page table, per-slot positions, pool refcounts —
   prove the CURRENT tick's write targets are pairwise distinct and
   unshared. Pure numpy on host mirrors; run it between polls or in a
   chaos soak.
2. **symbolic jaxpr check** (`check_tick_jaxpr`): over the traced
   decode-tick program, prove every write into a pool buffer derives
   its scatter indices from the page TABLE input (taint analysis) —
   a kernel that writes pool rows at indices not resolved through the
   table (the bug class the table indirection exists to prevent) is
   rejected at trace time, covering the XLA scatter appends AND a
   kernel's in-place update through a scalar-prefetch operand alike.
3. **shadow-page dynamic mode** (`snapshot_pool` / `check_shadow`):
   under interpret, snapshot the pool's bytes around ONE real tick and
   prove the changed-page set is contained in the expected write set
   (active slots' current pages + the trash sink). Catches what
   symbols cannot: a kernel whose index MATH is wrong.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np

from triton_dist_tpu.analysis import Report, eqn_src

_HERE = "triton_dist_tpu/analysis/races.py"


# ---------------------------------------------------------------------------
# 1. host-state write-exclusivity proof
# ---------------------------------------------------------------------------

def page_write_targets(table: np.ndarray, pos: np.ndarray,
                       page: int) -> np.ndarray:
    """Physical page each slot writes at its current position (every
    kv head of it: a page holds them all): [B] int32 (the exact
    resolution the paged append performs: table[slot, pos//page])."""
    B = pos.shape[0]
    maxp = table.shape[1]
    tile = np.minimum(np.asarray(pos, np.int64) // page, maxp - 1)
    return table[np.arange(B), tile]


def check_state(table, pos, active, page: int, *,
                trash: int, refcount=None, shared=None,
                subject: str = "paged-state",
                report: Optional[Report] = None) -> Report:
    """Write-exclusivity + CoW discipline over one host-side snapshot.

    Four rules:
    - no two slots write one physical page this tick;
    - no slot writes a page that lies inside ANOTHER slot's mapped
      valid extent (tiles 0..pos//page) — that reader would see the
      writer's bytes, which is exactly what admission's boundary-page
      copy-on-write exists to prevent. NOTE a refcount of 2 alone is
      NOT a violation: a slot legitimately tail-extends the last page
      of a prefix the radix TREE also holds (readers are capped at
      the tree extent; only a deeper match boundary-copies).
    - with `refcount` (prefix_cache.RefcountedPages.refcount): a
      non-trash write target at refcount 0 is a freed page — the
      allocator may re-issue it mid-write.
    - with `shared` (the page set mapped by TWO OR MORE live slots —
      the KV-fork sharing set, models/structured.py): n slots holding
      those pages READ-ONLY is legal (that sharing is the point of
      fork), but any write target inside the set is a fork CoW
      violation — fork must boundary-copy before a fork's appends can
      land, exactly like admission's prefix-cache CoW.
    """
    if report is None:
        report = Report("races")
    table = np.asarray(table)
    pos = np.asarray(pos)
    active = np.asarray(active, bool)
    wp = page_write_targets(table, pos, page)
    maxp = table.shape[1]
    # per-slot mapped valid extent: the pages tiles 0..pos//page map
    extent: Dict[int, set] = {}
    for b in range(pos.shape[0]):
        if not active[b]:
            continue
        last = min(int(pos[b]) // page, maxp - 1)
        extent[b] = {int(p) for p in table[b, :last + 1]}
    owner: Dict[int, int] = {}
    for b in range(pos.shape[0]):
        if not active[b]:
            continue
        p = int(wp[b])
        if p == trash:
            continue
        if p in owner:
            ob = owner[p]
            report.add(
                "error", _HERE + ":check_state", subject,
                f"write race: slot {b} (pos {int(pos[b])})"
                f" and slot {ob} (pos {int(pos[ob])}) "
                f"both write physical page {p} this tick — one "
                f"stream's KV will corrupt the other's")
        else:
            owner[p] = b
        for ob, pages in extent.items():
            if ob != b and p in pages:
                report.add(
                    "error", _HERE + ":check_state", subject,
                    f"CoW violation: slot {b} writes page "
                    f"{p} which slot {ob}'s table maps inside its "
                    f"valid extent (pos {int(pos[ob])}) — the "
                    f"reader sees the writer's bytes; admission "
                    f"must boundary-copy before mapping a shared "
                    f"page writable")
        if refcount is not None and refcount(p) == 0:
            report.add(
                "error", _HERE + ":check_state", subject,
                f"write to freed page: slot {b} writes "
                f"page {p} at refcount 0 — the allocator may "
                f"re-issue it to another slot mid-write")
        if shared is not None and p in shared:
            report.add(
                "error", _HERE + ":check_state", subject,
                f"fork CoW violation: slot {b} writes "
                f"page {p} which two or more live slots map "
                f"(fork-shared prefix KV) — a fork's appends must "
                f"land on a boundary-copied page, never the "
                f"shared original (every sibling reads it)")
    report.covered.append(subject)
    return report


def check_scheduler(sched, report: Optional[Report] = None) -> Report:
    """check_state over a live PagedDecodeSlots/ContinuousScheduler
    (device table+pos are tiny: one coalesced device_get). Fork-aware:
    the pages mapped by two or more live slots' host page mirrors
    form the `shared` set — KV-fork siblings reading them is legal,
    any write target among them fires. Also re-proves the pool
    conservation invariant as a finding instead of an assert."""
    import jax
    if report is None:
        report = Report("races")
    slots = getattr(sched, "slots", sched)   # ContinuousScheduler wraps
    table, pos, active = jax.device_get(
        (slots.cache.table, slots.pos, slots.active))
    pool = slots.prefix.pool
    # fork sharing set: a page counted once per live slot that maps it
    holders: Dict[int, int] = {}
    for b, pages in enumerate(getattr(slots, "_pages", ())):
        if b < len(active) and active[b]:
            for p in set(pages):
                holders[p] = holders.get(p, 0) + 1
    shared = {p for p, c in holders.items() if c >= 2}
    check_state(table, pos, active, slots.page,
                trash=slots.cache.trash, refcount=pool.refcount,
                shared=shared, subject=type(slots).__name__,
                report=report)
    if pool.available + pool.outstanding != pool.num_pages:
        report.add(
            "error", _HERE + ":check_scheduler", type(slots).__name__,
            f"pool conservation violated: {pool.available} free + "
            f"{pool.outstanding} outstanding != {pool.num_pages} total "
            f"(a page leaked or was double-mapped)")
    return report


# ---------------------------------------------------------------------------
# 2. symbolic jaxpr proof: pool writes derive their indices from the table
# ---------------------------------------------------------------------------

_SCATTER_PRIMS = ("scatter", "scatter-add", "scatter-mul", "scatter-min",
                  "scatter-max", "dynamic_update_slice")
# buffer identity survives these (the result IS the pool buffer,
# updated); anything else (dot, gather, reduce) produces derived data
_BUF_CARRY_PRIMS = _SCATTER_PRIMS + ("convert_element_type", "copy",
                                     "select_n", "transpose", "reshape")


def _subjaxprs_with_mapping(eqn):
    """(closed_jaxpr, invar_map) pairs for call-like eqns: invar_map[i]
    = index into eqn.invars feeding body invar i (None = no direct
    operand, e.g. scan's per-step slice keeps the same position)."""
    import jax.extend.core as jc
    prim = eqn.primitive.name
    out = []
    if prim in ("pjit", "closed_call", "core_call", "xla_call",
                "remat", "checkpoint", "custom_jvp_call",
                "custom_vjp_call", "custom_vjp_call_jaxpr"):
        jx = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
        if jx is not None:
            body = jx.jaxpr if isinstance(jx, jc.ClosedJaxpr) else jx
            out.append((body, list(range(len(eqn.invars)))))
    elif prim == "scan":
        body = eqn.params["jaxpr"].jaxpr
        out.append((body, list(range(len(eqn.invars)))))
    elif prim == "while":
        for k in ("cond_jaxpr", "body_jaxpr"):
            body = eqn.params[k].jaxpr
            out.append((body, list(range(len(eqn.invars)))))
    elif prim == "cond":
        for br in eqn.params["branches"]:
            # invars[0] is the predicate; branches see invars[1:]
            out.append((br.jaxpr, [i + 1 for i in
                                   range(len(eqn.invars) - 1)]))
    elif prim == "shard_map":
        body = eqn.params["jaxpr"]
        body = body.jaxpr if isinstance(body, jc.ClosedJaxpr) else body
        out.append((body, list(range(len(eqn.invars)))))
    return out


def _taint_jaxpr(jaxpr, table_in: set, buf_in: set, findings: list,
                 subject: str, depth: int = 0):
    """One pass over `jaxpr`: table_in/buf_in are sets of invar
    INDICES tainted on entry. Returns (table_out, buf_out) outvar index
    sets. Appends (src, message) findings for table-bypassing pool
    writes."""
    from jax.extend.core import Literal
    table_t = {jaxpr.invars[i] for i in table_in if i < len(jaxpr.invars)}
    buf_t = {jaxpr.invars[i] for i in buf_in if i < len(jaxpr.invars)}

    def tt(v):
        return not isinstance(v, Literal) and v in table_t

    def bt(v):
        return not isinstance(v, Literal) and v in buf_t

    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        subs = _subjaxprs_with_mapping(eqn)
        if subs:
            n_out_t, n_out_b = set(), set()
            for body, imap in subs:
                t_in = {bi for bi, oi in enumerate(imap)
                        if oi is not None and oi < len(eqn.invars)
                        and tt(eqn.invars[oi])}
                b_in = {bi for bi, oi in enumerate(imap)
                        if oi is not None and oi < len(eqn.invars)
                        and bt(eqn.invars[oi])}
                # scan/while bodies have extra leading invars on
                # mismatch; clamp handled inside by index bound check
                ot, ob = _taint_jaxpr(body, t_in, b_in, findings,
                                      subject, depth + 1)
                n_out_t |= ot
                n_out_b |= ob
            for i, v in enumerate(eqn.outvars):
                if i in n_out_t or (n_out_t and prim in
                                    ("while", "cond")):
                    table_t.add(v)
                if i in n_out_b:
                    buf_t.add(v)
            # conservative: any tainted input to an opaque call taints
            # table-taint of all outputs (over-taint never FAILS a
            # clean program; it only widens what counts as
            # table-derived)
            if any(tt(v) for v in eqn.invars):
                table_t.update(eqn.outvars)
            continue
        if prim == "pallas_call":
            aliased = {i for i, _ in
                       (eqn.params.get("input_output_aliases") or ())}
            gm = eqn.params.get("grid_mapping")
            n_idx = gm.num_index_operands if gm is not None else 0
            for i, v in enumerate(eqn.invars):
                if not bt(v):
                    continue
                if i in aliased:
                    # in-place pool update inside a kernel: its
                    # write offsets ride the scalar-prefetch
                    # operand, which must be table-derived
                    if n_idx and not any(tt(eqn.invars[j])
                                         for j in range(n_idx)):
                        findings.append((
                            eqn_src(eqn),
                            "pallas kernel updates a pool buffer "
                            "in-place but its scalar-prefetch operand "
                            "does not derive from the page table: the "
                            "in-kernel write offsets bypass the table "
                            "(write-exclusivity unprovable)"))
                # read-only pool operand: fine
            # outputs aliased from tainted inputs keep buffer identity
            for i, o in (eqn.params.get("input_output_aliases") or ()):
                if i < len(eqn.invars) and bt(eqn.invars[i]):
                    if o < len(eqn.outvars):
                        buf_t.add(eqn.outvars[o])
            if any(tt(v) for v in eqn.invars):
                table_t.update(eqn.outvars)
            continue
        if prim in _SCATTER_PRIMS and bt(eqn.invars[0]):
            idx_ops = eqn.invars[1:2] if prim.startswith("scatter") \
                else eqn.invars[2:]
            # scatter: (operand, indices, updates); DUS: (operand,
            # update, *start_indices)
            if prim == "dynamic_update_slice":
                idx_ops = eqn.invars[2:]
            if not any(tt(v) or isinstance(v, Literal)
                       for v in idx_ops):
                findings.append((
                    eqn_src(eqn),
                    f"pool write bypasses the page table: {prim} into "
                    f"a pool buffer with indices not derived from the "
                    f"table input — write exclusivity cannot be "
                    f"guaranteed for this update"))
        # ordinary taint propagation
        if any(tt(v) for v in eqn.invars):
            table_t.update(eqn.outvars)
        if prim in _BUF_CARRY_PRIMS and bt(eqn.invars[0]):
            buf_t.add(eqn.outvars[0])

    out_t = {i for i, v in enumerate(jaxpr.outvars)
             if not isinstance(v, Literal) and v in table_t}
    out_b = {i for i, v in enumerate(jaxpr.outvars)
             if not isinstance(v, Literal) and v in buf_t}
    return out_t, out_b


def check_tick_jaxpr(fn, args, pcache, subject: str,
                     report: Optional[Report] = None) -> Report:
    """Symbolic write-exclusivity proof over one traced tick program.

    fn(*args) must take the paged cache somewhere in `args` (the SAME
    pcache object, for leaf identification by object identity)."""
    import jax
    if report is None:
        report = Report("races")
    jaxpr = jax.make_jaxpr(fn)(*args)
    flat, _ = jax.tree_util.tree_flatten(args)
    pool_ids = {id(x) for x in
                list(pcache.pages_k) + list(pcache.pages_v)
                + list(getattr(pcache, "scales_k", ()) or ())
                + list(getattr(pcache, "scales_v", ()) or ())}
    table_idx = {i for i, x in enumerate(flat)
                 if x is pcache.table}
    buf_idx = {i for i, x in enumerate(flat) if id(x) in pool_ids}
    if not table_idx or not buf_idx:
        report.add("error", _HERE + ":check_tick_jaxpr", subject,
                   "could not locate the page table / pool buffers in "
                   "the program's flattened arguments (pass the same "
                   "pcache object the program was built with)")
        return report
    findings: list = []
    _taint_jaxpr(jaxpr.jaxpr, table_idx, buf_idx, findings, subject)
    for src, msg in findings:
        report.add("error", src, subject, msg)
    report.covered.append(subject)
    return report


def check_engine_tick(engine, batch: int = 2,
                      report: Optional[Report] = None) -> Report:
    """check_tick_jaxpr over the engine's canonical paged decode tick
    (the program PagedDecodeSlots drives every poll)."""
    import functools
    import jax.numpy as jnp
    from triton_dist_tpu.analysis.hotloop import tick_bodies
    if report is None:
        report = Report("races")
    model = engine.model
    pcache = engine.make_paged_slot_cache(batch)
    V = model.config.vocab_size
    logits0 = jnp.zeros((batch, V), jnp.float32)
    pos = jnp.zeros((batch,), jnp.int32)
    active = jnp.ones((batch,), bool)

    tick = functools.partial(tick_bodies(engine)["paged_slot_scan"],
                             gen_len=2)
    check_tick_jaxpr(tick, (model, logits0, pcache, pos, active),
                     pcache, f"paged_slot_scan[{engine.backend}]",
                     report)
    return report


# ---------------------------------------------------------------------------
# 3. shadow-page dynamic mode (interpret substrate)
# ---------------------------------------------------------------------------

def snapshot_pool(pcache) -> List[np.ndarray]:
    """Host snapshot of every layer's K/V (and scale) pool planes."""
    import jax
    bufs = list(pcache.pages_k) + list(pcache.pages_v) \
        + list(getattr(pcache, "scales_k", ()) or ()) \
        + list(getattr(pcache, "scales_v", ()) or ())
    return [np.asarray(x) for x in jax.device_get(bufs)]


def changed_pages(before: Sequence[np.ndarray],
                  after: Sequence[np.ndarray]) -> set:
    """Page ids whose bytes differ in ANY plane between snapshots."""
    out = set()
    for b, a in zip(before, after):
        if b.shape != a.shape:
            raise ValueError(f"snapshot shapes diverged: {b.shape} vs "
                             f"{a.shape}")
        diff = (b != a).reshape(b.shape[0], -1).any(axis=1)
        out.update(int(i) for i in np.nonzero(diff)[0])
    return out


def check_shadow(before, after, expected: set, *, trash: int,
                 subject: str = "shadow-tick",
                 report: Optional[Report] = None) -> Report:
    """Containment proof: pages changed by the tick ⊆ expected write
    set + trash. A page outside the set means some stream's write
    landed on KV it does not own — the dynamic form of the write race
    the state check proves symbolically."""
    if report is None:
        report = Report("races")
    stray = changed_pages(before, after) - set(expected) - {trash}
    for p in sorted(stray):
        report.add(
            "error", _HERE + ":check_shadow", subject,
            f"shadow-page violation: physical page {p} changed during "
            f"the tick but is not in the expected write set "
            f"(sorted head: {sorted(expected)[:8]}) — a stream wrote "
            f"KV it does not own")
    report.covered.append(subject)
    return report


def expected_write_pages(sched, steps: int) -> set:
    """The pages a `steps`-token decode chunk may legitimately write:
    each active slot's pages covering [pos, pos+steps), resolved
    through the live table (plus the trash sink, which check_shadow
    always allows)."""
    import jax
    slots = getattr(sched, "slots", sched)
    table, pos, active = jax.device_get(
        (slots.cache.table, slots.pos, slots.active))
    table = np.asarray(table)
    maxp = table.shape[1]
    out = set()
    for b in range(len(pos)):
        if not active[b]:
            continue
        for k in range(steps):
            tile = min((int(pos[b]) + k) // slots.page, maxp - 1)
            out.add(int(table[b, tile]))
    return out


def run(report: Optional[Report] = None) -> Report:
    """CLI entry: symbolic jaxpr proof over the canonical tiny engine's
    paged decode tick (the state/shadow modes need live scheduler
    state and run from the test suite / operator tooling)."""
    import jax
    from triton_dist_tpu.models import AutoLLM, Engine
    from triton_dist_tpu.models.config import tiny_qwen3
    if report is None:
        report = Report("races")
    mesh = jax.make_mesh((1,), ("tp",), devices=jax.devices()[:1])
    cfg = tiny_qwen3(1)
    model = AutoLLM.from_config(cfg, mesh)
    engine = Engine(model, max_seq=64, backend="flash")
    check_engine_tick(engine, report=report)
    return report
