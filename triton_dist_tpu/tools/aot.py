"""AOT compile/export of jitted programs.

TPU-native re-design of the reference AOT pipeline
(`python/triton_dist/tools/compile_aot.py:56` + `tools/runtime` — there
Triton kernels are pre-compiled to cubins and launched by a C runtime;
on TPU `jax.export` serializes the StableHLO of a jitted program —
including every Pallas/Mosaic kernel — and reloads it without retracing
Python, which is the whole point of the reference's AOT path (serving
processes that must not pay tracing/compile time)."""

from __future__ import annotations

import hashlib
import json
import os
import time
from typing import Any, Callable, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import export as jax_export


def aot_export(fn: Callable, args: Sequence[Any], *,
               platforms: Sequence[str] | None = None) -> bytes:
    """Trace + lower `fn` for `args` and serialize the result (the
    reference's compile_aot.py:56 product: a launchable artifact with
    no Python tracing at load time)."""
    exported = jax_export.export(
        jax.jit(fn),
        platforms=list(platforms) if platforms is not None else None,
    )(*args)
    return exported.serialize()


def aot_load(blob: bytes) -> Callable:
    """Deserialize an exported program into a callable (reference: the
    AOT runtime's launch entry, tools/runtime)."""
    exported = jax_export.deserialize(blob)
    return exported.call


def aot_roundtrip(fn: Callable, args: Sequence[Any], **kw) -> Callable:
    """Export + reload in one step (test/deployment convenience)."""
    return aot_load(aot_export(fn, args, **kw))


# ---------------------------------------------------------------------------
# AOT WARM START for the serving program set (ISSUE 12 / ROADMAP item
# 5): a disk cache over engine._jit_programs so a restarted server (or
# an elastically added worker) loads serialized programs instead of
# paying the compile storm. Two layers:
#
#   1. jax.export blobs, keyed on (program name, engine config, jax
#      version, argument avals, package-source epoch — a new build
#      over an old cache dir re-keys every blob instead of silently
#      serving stale programs): the warm process DESERIALIZES the
#      fully lowered StableHLO — python tracing never runs again;
#   2. jax's persistent compilation cache pointed at the same
#      directory: the XLA executable behind that StableHLO is reused
#      byte-for-byte, so the warm start compiles zero slot programs.
#
# Inputs are flattened to leaves before export (the model pytree's
# static auxdata — config, Mesh — has no serialized form), while
# OUTPUTS keep their pytree classes (KVCache / PagedSlotCache), whose
# treedefs register below with JSON-encoded auxdata. Programs the
# host cannot serialize (Pallas interpreter callbacks off-TPU) fall
# back to their live jit wrappers and are counted — the cache
# degrades, never breaks.
#
# Known trade: an exported program does not DONATE its inputs the way
# the live jit wrappers do, so an AOT-served tick transiently holds
# two copies of the KV carry on device. The cache exists for the
# restart path; long-running memory-tight servers can unset
# TDTPU_AOT_CACHE after warm start (the wrappers re-resolve lazily
# per engine) or accept the headroom.
# ---------------------------------------------------------------------------

_AOT_ENV = "TDTPU_AOT_CACHE"
_REGISTERED = False


def _register_pytree_serialization() -> None:
    """Register serializable treedefs for the cache classes slot
    programs RETURN (their auxdata is the static-field tuple of
    jax.tree_util.register_dataclass — JSON-safe ints/strings)."""
    global _REGISTERED
    if _REGISTERED:
        return
    from triton_dist_tpu.models.kv_cache import KVCache, PagedSlotCache

    def _ser(aux) -> bytes:
        return json.dumps(list(aux or ())).encode()

    def _des(b: bytes):
        return tuple(json.loads(b.decode()))

    for cls in (KVCache, PagedSlotCache):
        try:
            jax_export.register_pytree_node_serialization(
                cls, serialized_name=f"triton_dist_tpu.{cls.__name__}",
                serialize_auxdata=_ser, deserialize_auxdata=_des)
        except ValueError:
            pass          # already registered (idempotent re-import)
    _REGISTERED = True


def aot_cache_dir() -> str | None:
    """The TDTPU_AOT_CACHE convention: a non-empty value names the
    warm-start cache directory."""
    return os.environ.get(_AOT_ENV) or None


_CODE_EPOCH: str | None = None


def _code_epoch() -> str:
    """A fingerprint of the installed package source (relpath, size,
    mtime of every .py file), folded into every disk key: deploying a
    new build over an existing cache directory re-keys every blob, so
    a warm restart can never silently execute a STALE serialized
    program from the previous code version. mtime-based on purpose —
    cheap (one walk per process) and conservative (a fresh install
    invalidates even byte-identical files, which only costs one
    re-export)."""
    global _CODE_EPOCH
    if _CODE_EPOCH is None:
        import triton_dist_tpu
        root = os.path.dirname(os.path.abspath(
            triton_dist_tpu.__file__))
        h = hashlib.sha256()
        for dirpath, _, files in sorted(os.walk(root)):
            for fn in sorted(files):
                if not fn.endswith(".py"):
                    continue
                p = os.path.join(dirpath, fn)
                try:
                    st = os.stat(p)
                except OSError:
                    continue
                h.update(f"{os.path.relpath(p, root)}:{st.st_size}:"
                         f"{st.st_mtime_ns}".encode())
        _CODE_EPOCH = h.hexdigest()[:16]
    return _CODE_EPOCH


class AOTProgramCache:
    """Disk cache of exported serving programs (one per distinct
    (program, config, shapes) key). `wrap(name, jitted)` returns a
    drop-in callable: on the first call with a given argument
    signature it either DESERIALIZES the blob (warm start — no
    tracing) or exports the jitted program and saves it (cold start —
    one trace, shared with execution); every later call dispatches the
    resolved callable directly. Counters: `loaded` (programs served
    from disk), `exported` (cold saves), `fallback` (unserializable —
    ran on the live jit wrapper)."""

    def __init__(self, cache_dir: str, context: Tuple = ()):
        self.dir = cache_dir
        self.context = tuple(context)
        os.makedirs(cache_dir, exist_ok=True)
        self.loaded: list = []
        self.exported: list = []
        self.fallback: list = []
        self.load_s = 0.0        # deserialize time (warm)
        self.export_s = 0.0      # trace+export+serialize time (cold)
        self._mem: Dict[Tuple, Callable] = {}
        _register_pytree_serialization()
        # layer 2: the persistent XLA compilation cache (executables
        # keyed on HLO hash) shares the directory — on jax builds
        # without it, the export blobs still skip the retrace. A cache
        # dir the USER already configured is left alone (their shared
        # warm cache serves the same purpose); we only claim the
        # process-global knob when nobody else has, and remember what
        # we displaced so release_compilation_cache() can undo it.
        # On the main path this claim never fires:
        # initialize_distributed() has placed the cache already
        # (runtime/bootstrap.py::place_compile_cache). It is left for
        # a process that builds an AOT cache without a context.
        self._prev_cache_cfg: Tuple | None = None
        try:
            if not getattr(jax.config, "jax_compilation_cache_dir",
                           None):
                self._prev_cache_cfg = (
                    getattr(jax.config, "jax_compilation_cache_dir",
                            None),
                    getattr(jax.config,
                            "jax_persistent_cache_min_compile_time_"
                            "secs", None))
                jax.config.update("jax_compilation_cache_dir",
                                  cache_dir)
                jax.config.update(
                    "jax_persistent_cache_min_compile_time_secs", 0.0)
        except Exception:
            pass

    def release_compilation_cache(self) -> None:
        """Undo the process-global compilation-cache claim (a no-op
        when this cache never claimed it — e.g. a user cache dir was
        already configured, or another AOTProgramCache claimed first).
        Call before deleting a TEMPORARY cache directory, so the rest
        of the process never writes XLA cache entries into a dead
        path; long-lived servers just leave the claim in place."""
        if self._prev_cache_cfg is None:
            return
        prev_dir, prev_min = self._prev_cache_cfg
        self._prev_cache_cfg = None
        try:
            jax.config.update("jax_compilation_cache_dir", prev_dir)
            if prev_min is not None:
                jax.config.update(
                    "jax_persistent_cache_min_compile_time_secs",
                    prev_min)
        except Exception:
            pass

    def _disk_key(self, name: str, sig, treedef, kw) -> str:
        # platform + device count in the key: a shared cache dir may
        # serve CPU smoke runs and TPU fleets side by side — a blob
        # lowered for one platform must never be the other's hit
        src = repr((name, self.context, sorted(kw.items()),
                    str(treedef), sig, jax.__version__,
                    jax.default_backend(), jax.device_count(),
                    _code_epoch()))
        return hashlib.sha256(src.encode()).hexdigest()[:24]

    def _resolve(self, name: str, jitted: Callable, leaves, treedef,
                 sig, kw) -> Callable:
        import tempfile
        path = os.path.join(
            self.dir, f"{name}-{self._disk_key(name, sig, treedef, kw)}"
                      ".jexp")
        if os.path.exists(path):
            # a truncated/corrupt/foreign blob must DEGRADE (fall
            # through to export-or-live), never crash the restart —
            # the whole-module contract
            try:
                t0 = time.perf_counter()
                with open(path, "rb") as f:
                    exported = jax_export.deserialize(f.read())
                fn = jax.jit(exported.call)
                self.load_s += time.perf_counter() - t0
                self.loaded.append(name)
                return fn
            except Exception:
                try:
                    os.unlink(path)      # poison — re-export below
                except OSError:
                    pass
        try:
            t0 = time.perf_counter()

            def flat_fn(*flat):
                a = jax.tree_util.tree_unflatten(treedef, flat)
                return jitted(*a, **kw)

            exported = jax_export.export(jax.jit(flat_fn))(*leaves)
            blob = exported.serialize()
            # unique temp + atomic rename: concurrent cold-starting
            # workers sharing the dir must never publish each other's
            # half-written bytes under the final name
            fd, tmp = tempfile.mkstemp(dir=self.dir,
                                       suffix=".tmp")
            with os.fdopen(fd, "wb") as f:
                f.write(blob)
            os.replace(tmp, path)
            fn = jax.jit(exported.call)
            self.export_s += time.perf_counter() - t0
            self.exported.append(name)
            return fn
        except Exception:
            # unserializable on this substrate (e.g. Pallas interpret
            # callbacks off-TPU): run the live jit wrapper
            self.fallback.append(name)

            def live(*flat):
                a = jax.tree_util.tree_unflatten(treedef, flat)
                return jitted(*a, **kw)

            return live

    def wrap(self, name: str, jitted: Callable) -> Callable:
        """The per-call fast path flattens ONCE (the leaves are what
        the resolved callable consumes anyway) and memoizes on a
        hashable (name, static kw, treedef, shapes/dtypes) key — the
        sha256 disk key and any repr of the treedef are computed only
        on the first resolution of each signature (distinct prefill
        buckets resolve independently)."""
        def call(*args, **kw):
            leaves, treedef = jax.tree_util.tree_flatten(args)
            sig = tuple((jnp.shape(l), jnp.result_type(l))
                        for l in leaves)
            fk = (name, tuple(sorted(kw.items())), treedef, sig)
            fn = self._mem.get(fk)
            if fn is None:
                fn = self._resolve(name, jitted, leaves, treedef, sig,
                                   kw)
                self._mem[fk] = fn
            return fn(*leaves)

        call.__name__ = f"aot_{name}"
        return call

    def stats(self) -> dict:
        return {
            "dir": self.dir,
            "loaded": len(self.loaded),
            "exported": len(self.exported),
            "fallback": len(self.fallback),
            "loaded_names": sorted(set(self.loaded)),
            "exported_names": sorted(set(self.exported)),
            "fallback_names": sorted(set(self.fallback)),
            "load_s": round(self.load_s, 4),
            "export_s": round(self.export_s, 4),
        }


def wrap_serving_programs(progs: Dict[str, Callable], *,
                          context: Tuple = ()):
    """Engine hook: with TDTPU_AOT_CACHE set, wrap every jitted
    serving program in one AOTProgramCache (fresh per Engine — its
    counters describe THAT engine's warm start); otherwise return the
    programs untouched at zero overhead. Returns (programs, cache or
    None)."""
    d = aot_cache_dir()
    if not d:
        return progs, None
    cache = AOTProgramCache(d, context=context)
    return {k: cache.wrap(k, v) for k, v in progs.items()}, cache
