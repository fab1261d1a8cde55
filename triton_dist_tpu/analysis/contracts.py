"""Kernel contract analyzer (tdcheck checker 1).

Walks the jaxpr of every registered kernel wrapper
(kernels.kernel_registry) at its canonical sample shapes — a pure
trace, nothing executes — and checks, per pallas_call:

- **VMEM budget**: the per-grid-step footprint estimate (pipelined
  operand blocks double-buffered + VMEM scratch) must fit the chip's
  VMEM (~16 MiB/core, pallas_guide). An over-budget kernel compiles on
  the interpreter substrate and dies (or silently spills) on the chip —
  exactly the class of break the CPU suite cannot see.
- **block divisibility**: a pipelined BlockSpec whose block shape does
  not divide its array shape makes Mosaic pad trailing blocks — with
  OOB garbage flowing into reductions unless the kernel masks. The
  repo's kernels all pick dividing blocks on purpose (e.g.
  swiglu's _pick loop); a non-dividing block is a refactor regression.
- **in-place donation**: a kernel registered with `inplace=((in, out),
  ...)` (kv_update's aliased cache, kv_cache_scatter's window buffer)
  must actually carry those input_output_aliases in its trace — a
  dropped alias silently doubles the buffer's HBM traffic and
  allocation.

Every diagnostic carries the pallas_call kernel's file:line (its
debug_info), so a finding lands in the kernel source, not in the
analyzer.
"""

from __future__ import annotations

import math
from typing import Optional

from triton_dist_tpu.analysis import (Report, eqn_src, iter_eqns,
                                      pallas_kernel_name)

# ~16 MiB/core (pallas_guide "VMEM ~16 MB/core"); the estimate below
# is deliberately conservative (counts double buffering) so a kernel
# flagged here is genuinely close to the edge on a v5e core.
DEFAULT_VMEM_BUDGET = 16 << 20


def _dtype_size(dt) -> int:
    import jax.numpy as jnp
    try:
        return jnp.dtype(dt).itemsize
    except Exception:
        return 4


def _block_dims(bm) -> tuple:
    """A block mapping's per-dimension block sizes (pl.Blocked) as
    ints; a pl.Squeezed dimension has no block_size and reports None."""
    return tuple(getattr(d, "block_size", None) for d in bm.block_shape)


def _block_bytes(block_shape, dtype) -> int:
    n = 1
    for d in block_shape:
        n *= 1 if d is None else int(d)    # None: squeezed, one row
    return n * _dtype_size(dtype)


def _io_and_scratch_vars(eqn):
    gm = eqn.params["grid_mapping"]
    inner = eqn.params["jaxpr"]
    # inner invars: [scalar-prefetch] + inputs + outputs + scratch
    n_idx = gm.num_index_operands
    n_io = gm.num_inputs + gm.num_outputs
    return inner.invars[n_idx:n_idx + n_io], inner.invars[n_idx + n_io:]


def _unpipelined(var) -> bool:
    space = str(getattr(var.aval, "memory_space", None)).lower()
    # unpipelined HBM operand (comm kernels) / scalars: no VMEM block,
    # no divisibility contract
    return "any" in space or "smem" in space or "semaphore" in space


def eqn_vmem(eqn) -> int:
    """Per-grid-step VMEM estimate (bytes) of ONE pallas_call eqn:
    pipelined operand blocks (double-buffered when the grid actually
    pipelines) plus VMEM scratch — the single footprint model shared by
    the contract checker and `estimate_vmem` (the sweep pruner)."""
    gm = eqn.params["grid_mapping"]
    io_vars, scratch_vars = _io_and_scratch_vars(eqn)
    nsteps = math.prod(int(g) for g in gm.grid) if gm.grid else 1
    vmem = 0
    for bm, var in zip(gm.block_mappings, io_vars):
        if _unpipelined(var):
            continue
        bb = _block_bytes(_block_dims(bm), bm.array_aval.dtype)
        # Pallas double-buffers pipelined blocks (grid>1): 2x per operand
        vmem += bb * (2 if nsteps > 1 else 1)
    for var in scratch_vars:
        space = str(getattr(var.aval, "memory_space", None)).lower()
        if "vmem" in space:
            vmem += _block_bytes(var.aval.shape, var.aval.dtype)
    return vmem


def estimate_vmem(fn, args) -> int:
    """Public VMEM-footprint API (ISSUE 16): trace `fn(*args)` (a pure
    trace — nothing executes, no device memory is touched) and return
    the MAX per-grid-step VMEM estimate in bytes over every pallas_call
    in the trace — exactly the model the contract checker gates on.
    Returns 0 when the trace contains no pallas_call (XLA-only fn)."""
    import jax
    jaxpr = jax.make_jaxpr(fn)(*args)
    return max((eqn_vmem(e)
                for e in iter_eqns(jaxpr.jaxpr, "pallas_call")),
               default=0)


def analyze_pallas_eqn(eqn, report: Report, kernel_name: str,
                       budget: int) -> dict:
    """Contract checks for ONE pallas_call eqn; returns the extracted
    facts. The in-place-donation contract is enforced by check_kernel
    (aliases may live on ANY pallas_call of a kernel's trace)."""
    gm = eqn.params["grid_mapping"]
    src = eqn_src(eqn)
    body_name = pallas_kernel_name(eqn)
    subject = f"{kernel_name}/{body_name}"

    io_vars, _ = _io_and_scratch_vars(eqn)
    vmem = eqn_vmem(eqn)
    pipelined = 0
    blocks = []
    for bm, var in zip(gm.block_mappings, io_vars):
        space = str(getattr(var.aval, "memory_space", None)).lower()
        arr = bm.array_aval
        dims = _block_dims(bm)
        rec = dict(block=dims, array=tuple(arr.shape),
                   dtype=str(arr.dtype), space=space)
        blocks.append(rec)
        if _unpipelined(var):
            continue
        pipelined += 1
        for bdim, adim in zip(dims, arr.shape):
            if bdim is None:
                continue
            if bdim > int(adim) or int(adim) % bdim:
                report.add(
                    "error", src, subject,
                    f"block shape {dims} does not "
                    f"divide array shape {tuple(arr.shape)} "
                    f"(dim {bdim} vs {int(adim)}): Mosaic pads the "
                    f"trailing block and unmasked reductions read "
                    f"garbage")
                break

    if vmem > budget:
        report.add(
            "error", src, subject,
            f"per-grid-step VMEM estimate {vmem / (1 << 20):.2f} MiB "
            f"exceeds the {budget / (1 << 20):.0f} MiB budget "
            f"({pipelined} pipelined operands double-buffered + VMEM "
            f"scratch): shrink the BlockSpecs or raise the registry's "
            f"vmem_budget with a measured justification")

    aliases = set(eqn.params.get("input_output_aliases") or ())
    return dict(subject=subject, src=src, vmem=vmem, grid=tuple(gm.grid),
                blocks=blocks, aliases=sorted(aliases))


def check_kernel(spec, mesh, report: Optional[Report] = None) -> Report:
    """Trace one registered kernel and run the contract checks over
    every pallas_call in its jaxpr."""
    import jax
    if report is None:
        report = Report("contracts")
    fn, args = spec.build(mesh)
    jaxpr = jax.make_jaxpr(fn)(*args)
    budget = spec.vmem_budget or DEFAULT_VMEM_BUDGET
    eqns = list(iter_eqns(jaxpr.jaxpr, "pallas_call"))
    if not eqns:
        report.add("warning", f"triton_dist_tpu/{spec.module}",
                   spec.name,
                   "registered kernel traces to zero pallas_calls "
                   "(XLA fallback path? fix the sample shapes or the "
                   "registry entry)")
    pending = set(map(tuple, spec.inplace))
    for eqn in eqns:
        analyze_pallas_eqn(eqn, report, spec.name, budget)
        pending -= set(map(
            tuple, eqn.params.get("input_output_aliases") or ()))
    for pair in sorted(pending):
        report.add(
            "error", f"triton_dist_tpu/{spec.module}", spec.name,
            f"registered in-place kernel: no pallas_call in the trace "
            f"carries input_output_aliases {pair} — the donation was "
            f"dropped (the 'in-place' update now allocates and copies "
            f"a second buffer every call)")
    report.covered.append(spec.name)
    return report


def run(mesh=None, names=None) -> Report:
    """Contract-check the full registry (the tdcheck CLI entry)."""
    import jax
    from triton_dist_tpu.kernels import kernel_registry
    if mesh is None:
        n = len(jax.devices())
        mesh = jax.make_mesh((n,), ("tp",))
    ndev = mesh.shape["tp"]
    report = Report("contracts")
    for name, spec in kernel_registry().items():
        if names and name not in names:
            continue
        if spec.min_devices > ndev:
            continue
        try:
            check_kernel(spec, mesh, report)
        except Exception as e:  # a broken trace is itself a finding
            report.add("error", f"triton_dist_tpu/{spec.module}", name,
                       f"kernel failed to trace at its canonical "
                       f"sample shapes: {e!r}")
    return report
