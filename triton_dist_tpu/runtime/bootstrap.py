"""Distributed bootstrap: process-group init, device mesh construction,
and the global DistContext every op context hangs off.

TPU-native re-design of the reference bootstrap
(`initialize_distributed`, python/triton_dist/utils.py:302):

  reference                          | here
  -----------------------------------+------------------------------------
  torchrun env -> init_process_group | jax.distributed.initialize() from
  ("cpu:gloo,cuda:nccl")             | env (JAX service) when multi-host
  NCCL TP group                      | jax.sharding.Mesh over jax.devices()
  init_nvshmem_by_torch_process_grp  | nothing to do: ICI remote DMA needs
  (UID broadcast, symmetric heap)    | no heap map; "symmetric memory" is
                                     | identically-shaped per-device arrays
                                     | inside shard_map'ed Pallas kernels

The mesh is logically 1-D per parallelism axis; helpers build N-D meshes
("dp", "pp", "sp", "tp", "ep") the way the scaling-book recipe does.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from triton_dist_tpu.runtime.telemetry import install_compile_accounting

_CONTEXT: Optional["DistContext"] = None

# Default logical axis order: outermost (slowest, DCN-friendly) first,
# innermost (ICI-bandwidth-hungry) last — mirrors the megatron-style
# (dp, pp, ep, sp, tp) ordering the scaling-book recipe recommends.
DEFAULT_AXES: Tuple[str, ...] = ("dp", "pp", "ep", "sp", "tp")


@dataclasses.dataclass
class DistContext:
    """Global distributed state (reference analog: the module globals set up
    by utils.py:302-334 — TP_GROUP, nvshmem state, seeds)."""

    mesh: Mesh
    axes: Tuple[str, ...]
    seed: int = 42

    @property
    def num_devices(self) -> int:
        return self.mesh.size

    def axis_size(self, axis: str) -> int:
        return self.mesh.shape[axis] if axis in self.mesh.shape else 1

    def tp_size(self) -> int:
        return self.axis_size("tp")

    def submesh_spec(self, *axes: str) -> P:
        return P(*axes)


def _maybe_init_multihost() -> None:
    """Initialize the JAX distributed service when launched multi-host.

    The reference reads torchrun's env (RANK/WORLD_SIZE/MASTER_ADDR,
    utils.py:302-319); the JAX equivalents are coordinator env vars. This
    must run BEFORE any backend-initializing JAX call (jax.devices(),
    jax.process_count(), ...), so the decision is made from env/state only:

      - explicit JAX_COORDINATOR_ADDRESS + JAX_NUM_PROCESSES>1 ->
        initialize with them (torchrun-style launch);
      - TDTPU_MULTIHOST=1 -> argless initialize (Cloud TPU pod slice
        autodetection);
      - otherwise single-host, do nothing.
    """
    if jax.distributed.is_initialized():
        return
    coord = os.environ.get("JAX_COORDINATOR_ADDRESS") or os.environ.get(
        "COORDINATOR_ADDRESS")
    nprocs = os.environ.get("JAX_NUM_PROCESSES")
    if coord and nprocs and int(nprocs) > 1:
        jax.distributed.initialize(
            coordinator_address=coord,
            num_processes=int(nprocs),
            process_id=int(os.environ.get("JAX_PROCESS_ID", "0")),
        )
    elif os.environ.get("TDTPU_MULTIHOST") == "1":
        jax.distributed.initialize()


def make_mesh(mesh_shape: Optional[dict] = None,
              devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """Build a named device mesh.

    mesh_shape maps axis name -> size, e.g. {"dp": 2, "tp": 4}. Axes not
    mentioned get size 1 and are dropped. Default: all devices on "tp"
    (the reference's default is likewise one flat TP group over all ranks,
    utils.py:319).
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if mesh_shape is None:
        mesh_shape = {"tp": n}
    sizes = [s for s in mesh_shape.values()]
    names = [a for a in mesh_shape.keys()]
    total = int(np.prod(sizes)) if sizes else 1
    if total != n:
        raise ValueError(
            f"mesh shape {mesh_shape} needs {total} devices, have {n}")
    dev_array = np.asarray(devices).reshape(sizes)
    return Mesh(dev_array, tuple(names))


def auto_mesh(mesh: Mesh) -> Mesh:
    """`mesh` with every axis in Auto mode — the one mode this package's
    programs are written for (sharding carried by shard_map specs and
    NamedSharding placement, never by explicit-sharding types).
    `make_mesh` above already builds such a mesh; `jax.make_mesh` builds
    Explicit axes, so a model normalises the mesh it is handed once."""
    return Mesh(mesh.devices, mesh.axis_names)


# <checkout>/.jax_cache: fixed and derived from this file's own place,
# because the directory is part of every cache key's lookup — a path
# made from a temp name, a pid or the time never hits twice.
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
COMPILE_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def place_compile_cache() -> str:
    """Give JAX's persistent compilation cache a place, once, for every
    program of the process. Where JAX_COMPILATION_CACHE_DIR is set, JAX
    has already read it and nothing is touched; otherwise the cache goes
    to COMPILE_CACHE_DIR. Returns the directory in use."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return jax.config.jax_compilation_cache_dir


def initialize_distributed(mesh_shape: Optional[dict] = None,
                           seed: int = 42,
                           devices: Optional[Sequence[jax.Device]] = None,
                           ) -> DistContext:
    """Bootstrap (reference: utils.py:302). Idempotent per mesh shape."""
    global _CONTEXT
    _maybe_init_multihost()
    place_compile_cache()
    # before the caller builds weights: their compiles are counted too
    install_compile_accounting()
    mesh = make_mesh(mesh_shape, devices)
    _CONTEXT = DistContext(mesh=mesh, axes=tuple(mesh.axis_names), seed=seed)
    return _CONTEXT


def get_context() -> DistContext:
    if _CONTEXT is None:
        raise RuntimeError(
            "initialize_distributed() must be called first "
            "(reference contract: utils.py:302 — every test begins with it)")
    return _CONTEXT


def finalize_distributed() -> None:
    """Tear down (reference: utils.py:269). Releases the global context and
    the symmetric-workspace registry; the JAX runtime itself needs no
    explicit SHMEM finalize."""
    global _CONTEXT
    _CONTEXT = None
    from triton_dist_tpu.runtime import symm_mem
    symm_mem.clear_registry()


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


_NEXT_COLLECTIVE_ID = 0


def next_collective_id() -> int:
    """Allocate a fresh collective_id for a kernel family using the global
    barrier semaphore. Mosaic matches barrier semaphores across devices by
    collective_id, so two *different* concurrently-running collective
    kernels must not share one (reference analog: NVSHMEM's per-context
    signal buffers keeping ops' flags disjoint)."""
    global _NEXT_COLLECTIVE_ID
    cid = _NEXT_COLLECTIVE_ID
    _NEXT_COLLECTIVE_ID = (_NEXT_COLLECTIVE_ID + 1) % 16384
    return cid


def shmem_compiler_params(collective_id: Optional[int] = None,
                          n: Optional[int] = None, **kwargs):
    """CompilerParams for communication kernels.

    Mosaic only accepts `collective_id` when the kernel actually uses the
    global barrier semaphore (pltpu.get_barrier_semaphore); pass it ONLY
    for kernels calling dl.barrier_all. Pass `n` (the axis size) so the
    single-device degenerate case — where barrier_all is a no-op and the
    id must be dropped — is handled here once, not at every call site.
    All comm kernels need has_side_effects so XLA cannot DCE puts whose
    results flow through peers' memory rather than this device's outputs.
    """
    from jax.experimental.pallas import tpu as pltpu
    if n is not None and n <= 1:
        collective_id = None
    if collective_id is None:
        return pltpu.CompilerParams(has_side_effects=True, **kwargs)
    return pltpu.CompilerParams(has_side_effects=True,
                                collective_id=collective_id, **kwargs)


def interpret_mode():
    """Pallas interpret switch for the CPU test substrate.

    On real TPU: False (compile via Mosaic). Anywhere else: a TPU
    interpreter config so the *same* kernels (remote DMA, semaphores,
    barriers) execute on the virtual CPU mesh. Set
    TDTPU_DETECT_RACES=1 to turn on the interpreter's shared-memory race
    detector — the TPU answer to the reference's compute-sanitizer hook
    (launch.sh:160-163).
    """
    if on_tpu():
        return False
    from jax.experimental.pallas import tpu as pltpu
    from triton_dist_tpu.utils import env_flag
    return pltpu.InterpretParams(
        detect_races=env_flag("TDTPU_DETECT_RACES", False),
        dma_execution_mode="on_wait",
    )
