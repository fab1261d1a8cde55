"""Registry-driven autotuning sweep + tuned-config store (ISSUE 16,
ROADMAP item 5 — the layer that wires the substrate together).

The reference ships autotuning as a first-class layer (tune.py's
AutoTuner + JSON cache + cross-rank consensus); this module is the
TPU-shaped closing of that loop over the central kernel registry
(kernels.kernel_registry). For every kernel that declares a `tunables`
config space on its KernelSpec:

1. **prune** the space statically: each config is installed in the
   contextual profile (tools/tune._CONTEXTUAL — kernels re-read it at
   trace time), the canonical build is TRACED (nothing executes), and
   the tdcheck contracts checker (analysis/contracts.py — the VMEM
   footprint estimate behind `estimate_vmem` plus the block-
   divisibility rules) rejects configs that would OOM or pad on a real
   chip. A non-empty space that prunes to nothing raises — a typo'd
   space fails before any timing, Triton-autotune-prune style.
2. **time survivors** through tune.py's AutoTuner (JSON cache,
   cross-process consensus, shape-bucketed keys) at the registry's
   canonical shapes plus each declared shape-bucket variant.
3. **persist** the winner per (chip, kernel, shape-bucket) in a JSON
   store beside the AOT cache: `TDTPU_TUNE_CACHE` (file path) >
   `$TDTPU_AOT_CACHE/tune_cache.json` > ~/.triton_dist_tpu/.

Consumption: kernels resolve their schedule knobs as
    explicit arg > contextual profile > tune cache > hand-picked default
via `resolve_config(name, dims)`; with no cache installed the result is
{} and behavior is byte-identical to the hand-picked defaults. Tunable
axes are schedule-only by contract (KernelSpec docstring), so a cached
winner never changes emitted bytes either — only wall-clock.

CLI: ``python -m triton_dist_tpu.tools.sweep [--kernels a,b] [--dry-run]``
(tools/tune_smoke.sh is the bounded CPU smoke; tools/onchip_regen.sh
re-sweeps first when hardware returns).
"""

from __future__ import annotations

import argparse
import json
import os
from typing import Any, Dict, List, Optional, Sequence, Tuple

_STORE_ENV = "TDTPU_TUNE_CACHE"

# shape-generic bucket: kernels whose config is resolved with no shapes
# in scope (context creation) store and look up under this tag
GENERIC_BUCKET = "*"


def default_store_path() -> str:
    env = os.environ.get(_STORE_ENV)
    if env:
        return env
    aot = os.environ.get("TDTPU_AOT_CACHE")
    if aot:
        return os.path.join(aot, "tune_cache.json")
    return os.path.join(os.path.expanduser("~"), ".triton_dist_tpu",
                        "tune_cache.json")


# ----------------------------------------------------------------------
# Store: {chip_tag: {kernel: {bucket: {"cfg": {...}, ...}}}}
# ----------------------------------------------------------------------

_MEMO: Dict[str, Tuple[Tuple[int, int], dict]] = {}


def _load_store(path: str) -> dict:
    """Read (memoized on mtime/size: resolve_config runs at every trace,
    so repeated lookups must not re-read the file)."""
    try:
        st = os.stat(path)
        stamp = (st.st_mtime_ns, st.st_size)
    except OSError:
        return {}
    hit = _MEMO.get(path)
    if hit is not None and hit[0] == stamp:
        return hit[1]
    try:
        with open(path) as f:
            store = json.load(f)
    except (OSError, ValueError):
        store = {}
    _MEMO[path] = (stamp, store)
    return store


def store_update(path: str, chip: str, kernel: str, bucket: str,
                 entry: Dict[str, Any]) -> None:
    """Deep-merge ONE winner into the store under an exclusive lock:
    concurrent sweep processes union their (chip, kernel, bucket) cells
    instead of last-writer-wins; same-cell writes take the newest.

    Where POSIX flock is unavailable the merge runs unlocked: the
    tmp+rename still keeps readers from ever seeing a torn file, but
    two simultaneous writers can lose each other's cells (read-merge-
    write race). Sweeps on such platforms should serialize or use
    distinct --store paths."""
    if os.path.dirname(path):
        os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(f"{path}.lock", "w") as lf:
        try:
            import fcntl
            fcntl.flock(lf, fcntl.LOCK_EX)
        except (ImportError, OSError):
            pass               # no POSIX locks: atomic rename only
        try:
            with open(path) as f:
                disk = json.load(f)
        except (OSError, ValueError):
            disk = {}
        disk.setdefault(chip, {}).setdefault(kernel, {})[bucket] = entry
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(disk, f, indent=1, sort_keys=True)
        os.replace(tmp, path)


def tuned_choice(name: str, dims: Optional[Sequence[int]] = None,
                 path: Optional[str] = None) -> Optional[Dict[str, Any]]:
    """The swept winner for kernel `name` on this chip, or None.

    dims: the kernel's bucketing dims (same convention as the spec's
    tune_dims — see KernelSpec docstring); None looks up the
    shape-generic bucket. When the exact bucket was never swept but
    exactly ONE bucket was, that winner is returned — a schedule choice
    only: every consumer re-clamps blocks to legal divisors at its real
    shapes (paged_kv's block_w ladder, group_gemm's _pick, flash_attn's
    _pick_bx), so a cross-bucket fallback can degrade perf but never
    correctness. Constraint-bearing dims additionally belong IN the
    bucket key (the paged kernels lead with X=B*Hkv, whose slots B
    block_w must divide) so exact-bucket hits are legal by construction and the
    re-clamp stays a fallback, not the common path."""
    from triton_dist_tpu.tools.tune import _device_tag, shape_bucket
    path = path or default_store_path()
    per = _load_store(path).get(_device_tag(), {}).get(name)
    if not per:
        return None
    bucket = (shape_bucket(dims) if dims is not None else GENERIC_BUCKET)
    hit = per.get(bucket)
    if hit is None and len(per) == 1:
        hit = next(iter(per.values()))
    return dict(hit["cfg"]) if hit else None


def resolve_config(name: str, dims: Optional[Sequence[int]] = None
                   ) -> Dict[str, Any]:
    """The non-explicit half of a kernel's config resolution order:
    contextual profile (in-process override, tools/tune) > tune cache
    (this module's store) > {} (caller falls to its hand-picked
    default). Callers handle `explicit arg` above and defaults below."""
    from triton_dist_tpu.tools.tune import contextual_choice
    prof = contextual_choice(name)
    if prof is not None:
        return dict(prof)
    return tuned_choice(name, dims) or {}


# ----------------------------------------------------------------------
# Prune -> time -> persist
# ----------------------------------------------------------------------

def prune_space(spec, mesh) -> Tuple[List[dict], List[Tuple[dict, str]]]:
    """Statically prune spec.tunables BEFORE compiling or timing
    anything: per config, install it in the contextual profile, trace
    the canonical build, and run the tdcheck contracts checker over the
    trace — the same VMEM-footprint estimator behind
    analysis.contracts.estimate_vmem plus the block-divisibility rules
    (reused, never forked). A config whose trace raises is pruned too
    (illegal for the canonical shapes). Returns (survivors, rejected);
    raises when a non-empty space loses every config."""
    from triton_dist_tpu.analysis import contracts
    from triton_dist_tpu.tools.tune import contextual_override
    survivors: List[dict] = []
    rejected: List[Tuple[dict, str]] = []
    for cfg in spec.tunables:
        with contextual_override(spec.name, cfg):
            try:
                report = contracts.check_kernel(spec, mesh)
                errs = [f.message for f in report.findings
                        if f.severity == "error"]
            except Exception as e:
                errs = [f"failed to trace: {e!r}"]
        if errs:
            rejected.append((dict(cfg), errs[0]))
        else:
            survivors.append(dict(cfg))
    if spec.tunables and not survivors:
        raise ValueError(
            f"kernel_registry({spec.name!r}): every config of the "
            f"declared tunables space fails the VMEM/divisibility "
            f"pruner at the canonical shapes — the space is typo'd; "
            f"first rejection: {rejected[0][1]}")
    return survivors, rejected


def _cfg_key(cfg: Dict[str, Any]) -> str:
    return json.dumps(cfg, sort_keys=True)


def sweep_kernel(spec, mesh, *, iters: int = 2, warmup: int = 1,
                 force: bool = False, store_path: Optional[str] = None,
                 pruned: Optional[Tuple[List[dict],
                                        List[Tuple[dict, str]]]] = None
                 ) -> List[Dict[str, Any]]:
    """Prune, time and persist ONE kernel at its canonical shapes plus
    every declared shape-bucket variant. Returns one result dict per
    swept bucket ({"kernel", "bucket", "cfg", "cached", ...}).
    pruned: a prune_space(spec, mesh) result the caller already has
    (the CLI prints a summary first) — passing it skips re-tracing the
    whole config space."""
    import jax
    from triton_dist_tpu.tools import tune as _tune
    store_path = store_path or default_store_path()
    chip = _tune._device_tag()
    survivors, rejected = (pruned if pruned is not None
                           else prune_space(spec, mesh))
    results: List[Dict[str, Any]] = []
    for build in (spec.build,) + tuple(spec.variants):
        fn0, args0 = build(mesh)
        dims = spec.tune_dims(*args0) if spec.tune_dims else None
        bucket = (_tune.shape_bucket(dims) if dims is not None
                  else GENERIC_BUCKET)
        prior = (_load_store(store_path).get(chip, {})
                 .get(spec.name, {}).get(bucket))
        if prior is not None and not force:
            results.append(dict(kernel=spec.name, bucket=bucket,
                                chip=chip, cfg=dict(prior["cfg"]),
                                cached=True))
            continue
        time_s = None
        if len(survivors) == 1:
            winner = survivors[0]      # nothing to race
        else:
            # one jitted callable per surviving config, BUILT with the
            # config installed (the profile is read at trace/build
            # time) — the tune_comm_gemm_block_n pattern, so the timer
            # never measures Mosaic compile time or config plumbing
            jitted = {}
            for cfg in survivors:
                with _tune.contextual_override(spec.name, cfg):
                    f, a = build(mesh)
                    jitted[_cfg_key(cfg)] = (jax.jit(f), a)

            def run(*_probe, **cfg):
                f, a = jitted[_cfg_key(cfg)]
                return f(*a)

            tuner = _tune.AutoTuner(
                run, survivors, name=f"sweep:{spec.name}",
                iters=iters, warmup=warmup, bucket_shapes=True)
            winner = dict(tuner.pick(*args0))
            time_s = tuner._mem[tuner._key(args0, {})].get("time_s")
        entry = {"cfg": winner,
                 "time_us": (None if time_s is None
                             else round(time_s * 1e6, 3)),
                 "space": len(spec.tunables),
                 "pruned": len(rejected)}
        store_update(store_path, chip, spec.name, bucket, entry)
        results.append(dict(kernel=spec.name, bucket=bucket, chip=chip,
                            cfg=winner, cached=False,
                            time_us=entry["time_us"]))
    return results


# ----------------------------------------------------------------------
# CLI
# ----------------------------------------------------------------------

def main(argv: Optional[Sequence[str]] = None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m triton_dist_tpu.tools.sweep",
        description="Registry-driven autotuning sweep: prune declared "
                    "tunables with the tdcheck VMEM/divisibility "
                    "checker, time survivors, persist winners per "
                    "(kernel, shape-bucket, chip).")
    p.add_argument("--kernels", default=None,
                   help="comma-separated kernel subset (default: every "
                        "registry kernel with a tunables space)")
    p.add_argument("--dry-run", action="store_true",
                   help="enumerate + prune only; print the surviving "
                        "space, time nothing, store nothing")
    p.add_argument("--iters", type=int, default=2)
    p.add_argument("--warmup", type=int, default=1)
    p.add_argument("--force", action="store_true",
                   help="re-time buckets that already have a stored "
                        "winner")
    p.add_argument("--store", default=None,
                   help=f"store path (default: ${_STORE_ENV} > "
                        f"$TDTPU_AOT_CACHE/tune_cache.json > "
                        f"~/.triton_dist_tpu/tune_cache.json)")
    args = p.parse_args(argv)

    import jax
    from triton_dist_tpu.kernels import kernel_registry
    ndev = len(jax.devices())
    mesh = jax.make_mesh((ndev,), ("tp",))
    reg = kernel_registry()
    only = (None if args.kernels is None
            else [s.strip() for s in args.kernels.split(",") if s.strip()])
    if only:
        unknown = [n for n in only if n not in reg]
        if unknown:
            p.error(f"unknown kernels {unknown}; registry has "
                    f"{sorted(reg)}")
    store_path = args.store or default_store_path()
    rc = 0
    swept = 0
    for name, spec in reg.items():
        if only is not None and name not in only:
            continue
        if spec.min_devices > ndev:
            print(f"{name:28s} skipped (needs >= {spec.min_devices} "
                  f"devices, have {ndev})")
            continue
        if not spec.tunables:
            if only is not None or args.dry_run:
                print(f"{name:28s} no tunables (not swept)")
            continue
        try:
            survivors, rejected = prune_space(spec, mesh)
        except ValueError as e:
            print(f"{name:28s} ERROR: {e}")
            rc = 1
            continue
        line = (f"{name:28s} space={len(spec.tunables):2d} "
                f"pruned={len(rejected):2d} "
                f"surviving={len(survivors):2d}")
        if args.dry_run:
            print(line)
            for cfg in survivors:
                print(f"{'':28s}   keep  {_cfg_key(cfg)}")
            for cfg, why in rejected:
                print(f"{'':28s}   prune {_cfg_key(cfg)}  [{why}]")
            continue
        print(line)
        for res in sweep_kernel(spec, mesh, iters=args.iters,
                                warmup=args.warmup, force=args.force,
                                store_path=store_path,
                                pruned=(survivors, rejected)):
            swept += 1
            tag = ("cached" if res["cached"]
                   else (f"{res['time_us']:.1f}us"
                         if res.get("time_us") else "untimed"))
            print(f"{'':28s}   bucket {res['bucket']:12s} -> "
                  f"{_cfg_key(res['cfg'])}  [{tag}]")
    if not args.dry_run and swept:
        print(f"sweep: {swept} bucket(s) -> {store_path}")
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
