"""Device-side one-sided communication facade ("icishmem").

TPU-native re-design of the reference's OpenSHMEM-style device API
(`language/extra/libshmem_device.py`, surface documented at
docs/primitives.md:23-56). The reference dispatches ~80 functions to
NVSHMEM/rocSHMEM bitcode; on TPU the one-sided model is native to Pallas:

  reference (NVSHMEM)             | here (Pallas over ICI)
  --------------------------------+--------------------------------------
  my_pe() / n_pes()               | my_pe(axis) / n_pes(axis) via
                                  |   lax.axis_index/axis_size
  putmem_nbi(dst, src, pe)        | putmem_nbi -> make_async_remote_copy
  putmem_signal_nbi(.., sig, pe)  | putmem_signal -> remote copy whose
                                  |   recv_sem IS the signal flag
  signal_op(flag, v, SIG_ADD, pe) | signal_op -> pltpu.semaphore_signal
  signal_wait_until(flag, EQ, v)  | signal_wait_until -> semaphore_wait
  fence()/quiet()                 | quiet -> wait on outstanding send sems
  barrier_all() / sync_all()      | barrier_all -> neighbor barrier round
                                  |   on pltpu.get_barrier_semaphore()

All functions are meant to be called *inside* a Pallas kernel body that
runs under shard_map over a named mesh axis. Semaphores are explicit
arguments (Pallas scratch), because on TPU semaphores are typed hardware
resources, not addressable flag memory.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl  # noqa: F401  (re-exported)
from jax.experimental.pallas import tpu as pltpu


# --------------------------------------------------------------------------
# Trace-time comm recorder: with comm_trace() active, every facade call
# appends its STATIC structure (op kind, payload bytes, program order)
# while the kernel traces. Captures the per-device SPMD program exactly
# once (shard_map traces one program), with zero runtime overhead —
# tools/overlap_report.py uses it to build MULTICHIP_OVERLAP.md, the
# structural analog of the reference's per-op scaling traces.
# --------------------------------------------------------------------------

_COMM_TRACE = None
# Strong refs to every semaphore object seen during an active trace:
# event sem keys are id()s, and a collected ref's id can be REUSED by
# a later kernel's semaphore in the same block (observed as spurious
# cross-kernel ledger merges in multi-kernel ops like all_reduce_2d).
# Pinning the objects for the block's duration makes keys unique.
_COMM_TRACE_PINS = None


class comm_trace:
    """Capture the comm structure of kernels traced inside the block:

        with dl.comm_trace() as events:
            jax.jit(fn)(args)          # or plain call
        # events == [{"op": "put", "bytes": ..., ...}, ...]
    """

    def __enter__(self):
        global _COMM_TRACE, _COMM_TRACE_PINS
        self._prev = _COMM_TRACE
        self._prev_pins = _COMM_TRACE_PINS
        _COMM_TRACE = []
        _COMM_TRACE_PINS = []
        return _COMM_TRACE

    def __exit__(self, *exc):
        global _COMM_TRACE, _COMM_TRACE_PINS
        _COMM_TRACE = self._prev
        _COMM_TRACE_PINS = self._prev_pins
        return False


def _ref_bytes(ref):
    try:
        import math as _math
        n = _math.prod(ref.shape)
        return int(n) * jnp.dtype(ref.dtype).itemsize
    except Exception:
        return None


def _sem_key(sem):
    """Within-one-trace identity of a semaphore operand, so
    analysis/protocol.py can match set/wait pairs. `.at[...]` views
    (TransformedRef) unwrap to their base ref — the signal graph cares
    about the hardware semaphore, not the slice addressing it. The id
    is only meaningful inside a single `comm_trace` block (the same
    scratch ref object flows through one kernel trace)."""
    for _ in range(8):
        if type(sem).__name__ == "TransformedRef":
            sem = sem.ref
        else:
            break
    if _COMM_TRACE_PINS is not None:
        _COMM_TRACE_PINS.append(sem)
    return id(sem)


def _caller_src() -> str:
    """file:line of the facade call site (the innermost frame outside
    this module) — the diagnostic anchor analysis/protocol.py attaches
    to every signal-graph finding. Only computed while a comm_trace is
    active, so the facade stays free on ordinary traces."""
    import traceback
    for fr in reversed(traceback.extract_stack()):
        if "shmem_device" not in fr.filename:
            return f"{fr.filename}:{fr.lineno}"
    return "<unknown>"


def _emit(op: str, ref=None, **kw):
    if _COMM_TRACE is None:
        return
    ev = {"op": op, "src": _caller_src()}
    if ref is not None:
        ev["bytes"] = _ref_bytes(ref)
        ev["shape"] = tuple(getattr(ref, "shape", ()) or ())
    for k in ("send_sem", "recv_sem", "sem"):
        if k in kw and kw[k] is not None:
            kw[k] = _sem_key(kw[k])
    ev.update(kw)
    _COMM_TRACE.append(ev)


def my_pe(axis: str) -> jax.Array:
    """This device's rank along `axis` (ref: nvshmem_my_pe).

    On a size-1 axis this returns a CONCRETE zero: index arithmetic on
    it folds at trace time, so degenerate single-device rings emit
    static-offset DMA slices (a traced zero forces general
    dynamic-slice codegen, measured ~1.6x slower on the ag_gemm walk)."""
    if jax.lax.axis_size(axis) == 1:
        return jnp.int32(0)
    return jax.lax.axis_index(axis)


def n_pes(axis: str) -> jax.Array:
    """World size along `axis` (ref: nvshmem_n_pes)."""
    return jax.lax.axis_size(axis)


def ring_neighbors(axis: str):
    """(left, right) neighbor ranks along a ring on `axis`."""
    me = jax.lax.axis_index(axis)
    n = jax.lax.axis_size(axis)
    right = jax.lax.rem(me + 1, n)
    left = jax.lax.rem(me + n - 1, n)
    return left, right


def _device_id(pe, axis: Optional[str]):
    """Normalize a peer rank into a Pallas device_id.

    With `axis`, address by mesh coordinate ({axis: pe}, MESH type) so the
    peer is `pe` along that axis and *this device's own* coordinates along
    every other mesh axis — correct on N-D meshes (dp×tp etc.), where a
    flat LOGICAL id would cross shard groups. Without `axis`, `pe` is the
    flattened logical id (only correct on 1-D meshes).
    """
    if axis is None:
        return pe, pltpu.DeviceIdType.LOGICAL
    return {axis: pe}, pltpu.DeviceIdType.MESH


def putmem_nbi(dst_ref, src_ref, send_sem, recv_sem, pe,
               axis: Optional[str] = None) -> "pltpu.AsyncCopyDescriptor":
    """Non-blocking one-sided put: write src_ref (local) into dst_ref on
    device `pe` of the same kernel instance (ref: nvshmem_putmem_nbi_block,
    libshmem_device.py). Returns the descriptor; call .wait_send()/.wait()
    or use quiet() on the send semaphore."""
    _emit("put", src_ref, axis=axis, send_sem=send_sem, recv_sem=recv_sem)
    device_id, did_type = _device_id(pe, axis)
    rdma = pltpu.make_async_remote_copy(
        src_ref=src_ref, dst_ref=dst_ref,
        send_sem=send_sem, recv_sem=recv_sem,
        device_id=device_id, device_id_type=did_type)
    rdma.start()
    return rdma


def putmem_signal(dst_ref, src_ref, send_sem, recv_sem, pe,
                  axis: Optional[str] = None) -> "pltpu.AsyncCopyDescriptor":
    """Put-with-signal (ref: nvshmem_putmem_signal_nbi_block): on TPU the
    receive semaphore *is* the signal — the receiver's semaphore_wait on
    `recv_sem` is the `signal_wait_until` of the reference."""
    return putmem_nbi(dst_ref, src_ref, send_sem, recv_sem, pe, axis)


def local_copy(dst_ref, src_ref, sem) -> None:
    """Local async copy, blocking until complete (HBM<->VMEM staging).

    Deliberately NOT named getmem: Pallas has no one-sided remote *get*
    (remote DMA is put-only); the reference's getmem call sites map to
    either a put from the data owner or a pull expressed as
    putmem from the peer's program instance. Keeping the name honest
    avoids silently-local 'gets' in ported kernels.
    """
    _emit("local_copy", src_ref, sem=sem)
    dma = pltpu.make_async_copy(src_ref, dst_ref, sem)
    dma.start()
    dma.wait()


def local_copy_nbi(dst_ref, src_ref, sem):
    _emit("local_copy_nbi", src_ref, sem=sem)
    dma = pltpu.make_async_copy(src_ref, dst_ref, sem)
    dma.start()
    return dma


def signal_op(sem, inc: int = 1, pe=None, axis: Optional[str] = None) -> None:
    """Increment a (possibly remote) semaphore (ref: nvshmemx_signal_op
    with NVSHMEM_SIGNAL_ADD)."""
    _emit("signal", remote=pe is not None, axis=axis, sem=sem, inc=inc)
    if pe is None:
        pltpu.semaphore_signal(sem, inc=inc)
    else:
        device_id, did_type = _device_id(pe, axis)
        pltpu.semaphore_signal(sem, inc=inc, device_id=device_id,
                               device_id_type=did_type)


def signal_wait_until(sem, value: int) -> None:
    """Block until a REGULAR/BARRIER semaphore reaches `value`, consuming
    it (ref: nvshmem_signal_wait_until(EQ)). Pallas semaphore_wait
    decrements by `value`, which matches the reference's reset-after-wait
    idiom. For DMA-completion semaphores use dma_wait()."""
    _emit("sem_wait", sem=sem, value=value)
    pltpu.semaphore_wait(sem, value)


def dma_wait(sem, ref, count: int = 1) -> None:
    """Wait for `count` completed DMAs of `ref`'s byte size on a DMA
    semaphore. TPU DMA semaphores count *bytes*, so the wait is expressed
    by a descriptor of matching shape (the canonical Pallas idiom: a
    self-copy descriptor used only for its wait)."""
    _emit("dma_wait", ref, count=count, sem=sem)
    for _ in range(count):
        pltpu.make_async_copy(ref, ref, sem).wait()


def dma_wait_dyn(sem, ref, count) -> None:
    """dma_wait with a TRACED count (a fori_loop of waits): for kernels
    whose arrival count is data-dependent (e.g. kv_cache_scatter — how
    many blocks land in MY window depends on my rank). The comm trace
    records the wait as dynamic; analysis/protocol.py exempts the
    semaphore from exact set/wait balance but still checks ordering."""
    _emit("dma_wait_dyn", ref, sem=sem)

    def body(i, c):
        pltpu.make_async_copy(ref, ref, sem).wait()
        return c

    jax.lax.fori_loop(0, count, body, 0)


def wait(sem, value: int = 1):
    """`dl.wait` analog (ref: language/distributed_ops.py:57): wait for a
    per-tile signal and return a token ordering subsequent loads. On TPU
    semaphore_wait already orders the DMA's data, so the token is ()."""
    _emit("sem_wait", sem=sem, value=value)
    pltpu.semaphore_wait(sem, value)
    return ()


def consume_token(x, token):
    """`dl.consume_token` analog (ref: language/distributed_ops.py:74).
    A no-op on TPU — kept so kernel structure ports 1:1; Pallas semaphore
    waits already order DMA-delivered data."""
    del token
    return x


def quiet(send_sem, src_ref, count: int = 1) -> None:
    """Drain outstanding puts (ref: nvshmem_quiet): wait the send
    semaphore for `count` puts of `src_ref`'s byte size."""
    dma_wait(send_sem, src_ref, count)


def barrier_all(axis: str, barrier_sem=None) -> None:
    """Full barrier over the mesh axis (ref: nvshmem_barrier_all /
    barrier_all_intra_node). Dissemination barrier on the global barrier
    semaphore: ceil(log2(n)) rounds, each signaling rank +2^k and waiting
    for the matching signal — O(log n) ICI hops, no host involvement.

    Requires the enclosing pallas_call to set
    compiler_params=pltpu.CompilerParams(collective_id=...).
    """
    # single-device axis: a true no-op, BEFORE touching the barrier
    # semaphore (Mosaic pairs get_barrier_semaphore with a collective_id,
    # which single-device kernels must not pass)
    n_static = _static_axis_size(axis)
    _emit("barrier_all", axis=axis, n=n_static)
    if n_static <= 1 and barrier_sem is None:
        return
    sem = barrier_sem if barrier_sem is not None else pltpu.get_barrier_semaphore()
    me = jax.lax.axis_index(axis)
    n = jax.lax.axis_size(axis)
    # static unroll over log2 rounds: n is static at trace time
    import math
    rounds = max(1, math.ceil(math.log2(n_static))) if n_static > 1 else 0
    for k in range(rounds):
        dist = 1 << k
        dst = jax.lax.rem(me + dist, n)
        did, dtype = _device_id(dst, axis)
        pltpu.semaphore_signal(sem, inc=1, device_id=did,
                               device_id_type=dtype)
        pltpu.semaphore_wait(sem, 1)


def _static_axis_size(axis: str) -> int:
    """Axis size as a Python int (sizes are static under shard_map)."""
    return int(jax.lax.axis_size(axis))


def sem_value(sem) -> jax.Array:
    """Non-destructive semaphore read (ref: ld of the flag word)."""
    return pltpu.semaphore_read(sem)


# ---------------------------------------------------------------------------
# Collective device helpers (reference: the libshmem_device collective
# surface — broadcast/fcollect/teams, python/triton_dist/language/)
# ---------------------------------------------------------------------------

def broadcastmem(dst_ref, src_ref, root, axis: str, send_sem,
                 recv_sem) -> None:
    """In-kernel broadcast (ref: nvshmemx_broadcastmem_block): the root
    puts src_ref into dst_ref on every PE (itself included, keeping the
    control flow uniform); every PE waits exactly one arrival. Call on
    ALL PEs of the axis."""
    me = jax.lax.axis_index(axis)
    n = _static_axis_size(axis)

    @pl.when(me == root)
    def _send():
        for p in range(n):
            putmem_nbi(dst_ref, src_ref, send_sem, recv_sem,
                       jnp.int32(p), axis)

    pltpu.make_async_copy(src_ref, src_ref, recv_sem).wait()

    @pl.when(me == root)
    def _drain():
        quiet(send_sem, src_ref, n)


def fcollect(dst_ref, src_ref, axis: str, send_sem, recv_sem) -> None:
    """In-kernel allgather (ref: nvshmemx_fcollectmem_block): every PE
    puts its src_ref into slot `me` of dst_ref on every peer, then
    waits n arrivals. dst_ref rows = n * src_ref rows."""
    me = jax.lax.axis_index(axis)
    n = _static_axis_size(axis)
    rows = src_ref.shape[0]
    for p in range(n):
        putmem_nbi(dst_ref.at[pl.ds(me * rows, rows)], src_ref,
                   send_sem, recv_sem, jnp.int32(p), axis)
    for _ in range(n):
        pltpu.make_async_copy(src_ref, src_ref, recv_sem).wait()
    quiet(send_sem, src_ref, n)


def atomic_add(sem, value, pe=None, axis: Optional[str] = None) -> None:
    """Remote atomic add (ref: nvshmem AMO_ADD on flag words): TPU's
    remote atomics are semaphore increments — the flag-word AMO uses of
    the reference map 1:1 onto semaphore_signal with an amount."""
    signal_op(sem, value, pe, axis)


def atomic_read(sem) -> jax.Array:
    """Non-destructive flag read (ref: AMO_FETCH on a flag word)."""
    return sem_value(sem)


# Teams (ref: nvshmem teams / NVSHMEM_TEAM_WORLD + team_split): on a
# named device mesh, a "team" IS a mesh axis — my_pe(axis)/n_pes(axis)
# are the team-relative rank/size, and "team split" is mesh
# construction (jax.make_mesh((a, b), ("outer", "inner"))). These
# aliases keep ported kernel structure readable.
def team_my_pe(axis: str) -> jax.Array:
    return my_pe(axis)


def team_n_pes(axis: str) -> jax.Array:
    return n_pes(axis)
