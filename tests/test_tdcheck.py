"""tdcheck static analysis (ISSUE 15): clean-tree zero-violation scans
plus SEEDED-VIOLATION mutation tests — every checker must (a) pass the
real tree and (b) demonstrably FIRE, with a file:line-bearing
diagnostic, on a planted instance of the bug class it exists for. A
checker without a firing test is a checker that may be vacuously
green.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from triton_dist_tpu.analysis import (Report, contracts, deadcode,
                                      hotloop, protocol, races)
from triton_dist_tpu.kernels import KernelSpec, kernel_registry

mesh = None


def setup_module(module):
    global mesh
    mesh = jax.make_mesh((len(jax.devices()),), ("tp",))


def _errors(report):
    return [f.format() for f in report.errors]


# ---------------------------------------------------------------------------
# registry (the satellite): one enumeration for tdcheck/kprof/perf
# ---------------------------------------------------------------------------

def test_registry_enumerates_the_kernel_surface():
    reg = kernel_registry()
    assert len(reg) >= 25, sorted(reg)
    comm = [s for s in reg.values() if s.protocol is not None]
    assert len(comm) >= 15
    # kprof's phase table derives from the registry (one place)
    from triton_dist_tpu.tools.kprof_run import PHASES
    assert set(PHASES) == {"ag_group_gemm", "moe_reduce_rs", "ep_fused",
                           "gdn"}
    # perf_report's coverage check reads the same table
    from triton_dist_tpu.tools.perf_report import registry_coverage
    cov = registry_coverage(["all_gather(one_shot)", "flash_decode"])
    assert cov["kernels_registered"] == len(reg)
    assert "gdn_fwd" in cov["uncovered"]


def test_registry_builders_all_trace():
    """Every registered kernel's canonical sample traces (make_jaxpr
    only — the tdcheck contract scan's substrate)."""
    for name, spec in kernel_registry().items():
        if spec.min_devices > mesh.shape["tp"]:
            continue
        fn, args = spec.build(mesh)
        jax.make_jaxpr(fn)(*args)   # raises on a broken builder


# ---------------------------------------------------------------------------
# checker 1: kernel contracts
# ---------------------------------------------------------------------------

def test_contracts_clean_tree():
    r = contracts.run(mesh)
    assert not r.errors, _errors(r)
    assert len(r.covered) >= 25


def _pallas_ident(block, shape, grid=(4,)):
    from jax.experimental import pallas as pl

    def kern(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    def f(x):
        return pl.pallas_call(
            kern, grid=grid,
            in_specs=[pl.BlockSpec(block, lambda i: (0, 0))],
            out_specs=pl.BlockSpec(block, lambda i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct(shape, jnp.float32),
            interpret=True)(x)

    return f, (jnp.zeros(shape, jnp.float32),)


def test_contracts_flags_overbudget_vmem():
    """Seeded violation: a kernel staging 2x (2048, 2048) f32 blocks
    double-buffered (~64 MiB) must trip the ~16 MiB budget with the
    kernel's file:line in the diagnostic."""
    fn, args = _pallas_ident((2048, 2048), (2048, 2048))
    spec = KernelSpec("evil_vmem", "tests", "compute",
                      lambda m: (fn, args))
    r = contracts.check_kernel(spec, mesh)
    msgs = _errors(r)
    assert any("VMEM estimate" in m for m in msgs), msgs
    assert any("test_tdcheck.py:" in m for m in msgs), msgs


def test_contracts_estimate_vmem_public_api():
    """ISSUE 16: `estimate_vmem(fn, args)` is the sweep pruner's public
    entry into the contracts VMEM model. Exact arithmetic on a known
    kernel: (128, 128) f32 blocks in+out, grid=(4,) so both pipelined
    buffers double — 2 * 2 * 128*128*4 = 262144 bytes. A pallas-free
    fn estimates 0, and the number agrees with what check_kernel's
    walk prices (behavior unchanged by the refactor: the clean-tree
    test above still passes on the same model)."""
    fn, args = _pallas_ident((128, 128), (128, 128), grid=(4,))
    assert contracts.estimate_vmem(fn, args) == 2 * 2 * 128 * 128 * 4
    # grid=(1,): single-buffered, half the bytes
    fn1, args1 = _pallas_ident((128, 128), (128, 128), grid=(1,))
    assert contracts.estimate_vmem(fn1, args1) == 2 * 128 * 128 * 4
    assert contracts.estimate_vmem(lambda x: x + 1,
                                   (jnp.zeros((8, 8)),)) == 0


def test_contracts_flags_nondivisible_block():
    fn, args = _pallas_ident((48, 128), (128, 128))
    spec = KernelSpec("evil_blocks", "tests", "compute",
                      lambda m: (fn, args))
    r = contracts.check_kernel(spec, mesh)
    msgs = _errors(r)
    assert any("does not divide" in m for m in msgs), msgs
    assert any("test_tdcheck.py:" in m for m in msgs), msgs


def test_contracts_flags_dropped_inplace_alias():
    """A registered in-place kernel whose donation went missing."""
    fn, args = _pallas_ident((128, 128), (128, 128), grid=(1,))
    spec = KernelSpec("evil_alias", "tests", "compute",
                      lambda m: (fn, args), inplace=((0, 0),))
    r = contracts.check_kernel(spec, mesh)
    msgs = _errors(r)
    assert any("input_output_aliases" in m for m in msgs), msgs


# ---------------------------------------------------------------------------
# checker 3: comm protocol verifier
# ---------------------------------------------------------------------------

def _trace_broken(kernel_body, extra_scratch=()):
    """Trace a deliberately broken one-sided kernel under comm_trace
    (make_jaxpr only; the kernel never executes, so this runs on any
    substrate). Scratch: two DMA semaphores (send, recv) plus
    extra_scratch."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from jax.sharding import PartitionSpec as P
    from triton_dist_tpu import language as dl
    from triton_dist_tpu.runtime import (next_collective_id,
                                         shmem_compiler_params)
    n = mesh.shape["tp"]
    cid = next_collective_id()

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=P("tp"),
                       out_specs=P("tp"), check_vma=False)
    def f(x_loc):
        return pl.pallas_call(
            functools.partial(kernel_body, n),
            out_shape=jax.ShapeDtypeStruct(x_loc.shape, x_loc.dtype),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.SemaphoreType.DMA(()),
                            pltpu.SemaphoreType.DMA(())]
            + list(extra_scratch),
            compiler_params=shmem_compiler_params(cid, n=n),
        )(x_loc)

    x = jnp.zeros((8 * n, 128), jnp.float32)
    with dl.comm_trace() as events:
        jax.make_jaxpr(f)(x)
    return list(events)


def test_protocol_clean_tree():
    r = protocol.run(mesh)
    assert not r.errors, _errors(r)
    assert len(r.covered) >= 15


def test_protocol_flags_missing_recv_wait():
    """Puts whose arrivals are never awaited = landing-buffer race."""
    from triton_dist_tpu import language as dl

    def bad(n, x_ref, o_ref, send_sem, recv_sem):
        dl.barrier_all("tp")
        dl.putmem_nbi(o_ref, x_ref, send_sem, recv_sem, 0, "tp")
        dl.quiet(send_sem, x_ref, 1)      # drains sends, awaits nothing

    r = protocol.verify_events(_trace_broken(bad), "bad_no_wait")
    msgs = _errors(r)
    assert any("RECV semaphore" in m and "data race" in m
               for m in msgs), msgs
    assert any("test_tdcheck.py:" in m for m in msgs), msgs


def test_protocol_flags_missing_send_drain():
    from triton_dist_tpu import language as dl

    def bad(n, x_ref, o_ref, send_sem, recv_sem):
        dl.barrier_all("tp")
        dl.putmem_nbi(o_ref, x_ref, send_sem, recv_sem, 0, "tp")
        dl.dma_wait(recv_sem, x_ref, 1)   # awaits arrival, never drains

    r = protocol.verify_events(_trace_broken(bad), "bad_no_drain")
    msgs = _errors(r)
    assert any("SEND semaphore" in m and "quiet" in m
               for m in msgs), msgs


def test_protocol_flags_wait_before_set():
    from triton_dist_tpu import language as dl

    def bad(n, x_ref, o_ref, send_sem, recv_sem):
        dl.barrier_all("tp")
        dl.dma_wait(recv_sem, x_ref, 1)   # before ANY put: deadlock
        dl.putmem_nbi(o_ref, x_ref, send_sem, recv_sem, 0, "tp")
        dl.quiet(send_sem, x_ref, 1)

    r = protocol.verify_events(_trace_broken(bad), "bad_order")
    msgs = _errors(r)
    assert any("wait-before-set" in m for m in msgs), msgs


def test_protocol_flags_barrier_elision():
    from triton_dist_tpu import language as dl

    def bad(n, x_ref, o_ref, send_sem, recv_sem):
        dl.putmem_nbi(o_ref, x_ref, send_sem, recv_sem, 0, "tp")
        dl.dma_wait(recv_sem, x_ref, 1)
        dl.quiet(send_sem, x_ref, 1)

    r = protocol.verify_events(_trace_broken(bad), "bad_no_barrier")
    msgs = _errors(r)
    assert any("barrier_all" in m for m in msgs), msgs


def test_protocol_flags_dyn_wait_never_signaled():
    """A data-dependent arrival wait whose semaphore nothing signals:
    any rank with a nonzero runtime count deadlocks."""
    import jax.numpy as jnp
    from triton_dist_tpu import language as dl

    def bad(n, x_ref, o_ref, send_sem, recv_sem):
        dl.barrier_all("tp")
        dl.putmem_nbi(o_ref, x_ref, send_sem, send_sem, 0, "tp")
        dl.dma_wait_dyn(recv_sem, x_ref, jnp.int32(2))  # nobody signals
        dl.quiet(send_sem, x_ref, 2)

    r = protocol.verify_events(_trace_broken(bad), "bad_dyn")
    msgs = _errors(r)
    assert any("dma_wait_dyn" in m and "ever signals" in m
               for m in msgs), msgs


def test_protocol_flags_credit_imbalance():
    from jax.experimental.pallas import tpu as pltpu
    from triton_dist_tpu import language as dl

    def bad(n, x_ref, o_ref, send_sem, recv_sem, credit_sem):
        dl.barrier_all("tp")
        dl.putmem_nbi(o_ref, x_ref, send_sem, recv_sem, 0, "tp")
        dl.signal_op(credit_sem, 1, 0, "tp")   # credit granted...
        dl.dma_wait(recv_sem, x_ref, 1)
        dl.quiet(send_sem, x_ref, 1)           # ...never consumed

    events = _trace_broken(bad,
                           extra_scratch=[pltpu.SemaphoreType.REGULAR])
    r = protocol.verify_events(events, "bad_credit")
    msgs = _errors(r)
    assert any("credit imbalance" in m for m in msgs), msgs


# ---------------------------------------------------------------------------
# checker 2: paged-KV race detector
# ---------------------------------------------------------------------------

def _tiny_engine(backend="flash"):
    from triton_dist_tpu.models import AutoLLM, Engine
    from triton_dist_tpu.models.config import tiny_qwen3
    m1 = jax.make_mesh((1,), ("tp",), devices=jax.devices()[:1])
    cfg = tiny_qwen3(1)
    model = AutoLLM.from_config(cfg, m1)
    return cfg, Engine(model, max_seq=64, backend=backend)


def test_races_clean_tick_jaxpr():
    r = races.run()
    assert not r.errors, _errors(r)


def test_races_flags_write_collision():
    """Two slots mapped to one physical page at their write position."""
    table = np.arange(8, dtype=np.int32).reshape(2, 4)
    table[1, 0] = table[0, 0]            # slot 1's page == slot 0's
    r = races.check_state(table, np.zeros(2, np.int32),
                          np.ones(2, bool), 8, trash=15)
    msgs = _errors(r)
    assert any("write race" in m for m in msgs), msgs


def test_races_flags_cow_violation():
    """Slot 0's write page sits inside slot 1's mapped valid extent —
    the reader sees the writer's bytes (the exact hazard the
    boundary-page CoW exists to prevent)."""
    table = np.arange(8, dtype=np.int32).reshape(2, 4)
    table[1, 0] = 99  # decouple slot 1's write tile from slot 0's...
    table[1, 1] = table[0, 0]   # ...but its EXTENT maps slot 0's page
    r = races.check_state(table, np.asarray([0, 9], np.int32),
                          np.ones(2, bool), 8, trash=15)
    msgs = _errors(r)
    assert any("CoW violation" in m for m in msgs), msgs
    # a slot tail-extending a page only the radix TREE shares
    # (refcount 2, no other slot's extent) is the SANCTIONED path
    clean = races.check_state(np.arange(4, dtype=np.int32
                                        ).reshape(1, 4),
                              np.asarray([4], np.int32),
                              np.ones(1, bool), 8, trash=15,
                              refcount=lambda p: 2)
    assert not clean.errors, _errors(clean)


def test_races_flags_write_to_freed_page():
    table = np.arange(4, dtype=np.int32).reshape(1, 4)
    r = races.check_state(table, np.zeros(1, np.int32),
                          np.ones(1, bool), 8, trash=15,
                          refcount=lambda p: 0)
    msgs = _errors(r)
    assert msgs and all("freed page" in m for m in msgs), msgs


def test_races_flags_table_bypassing_write():
    """Symbolic jaxpr proof: a tick that scatters into the pool at
    indices NOT derived from the page table is rejected."""
    import dataclasses
    _, eng = _tiny_engine()
    pc = eng.make_paged_slot_cache(2)

    def evil(model, pc, pos):
        pk = tuple(p.at[jnp.arange(4), 0].set(0.0) for p in pc.pages_k)
        return dataclasses.replace(pc, pages_k=pk)

    r = races.check_tick_jaxpr(evil, (eng.model, pc,
                                      jnp.zeros(2, jnp.int32)),
                               pc, "evil_tick")
    msgs = _errors(r)
    assert any("bypasses the page table" in m for m in msgs), msgs

    def good(model, pc, pos):
        pidx = pc.table[jnp.arange(4), 0]
        pk = tuple(p.at[pidx, 0].set(0.0) for p in pc.pages_k)
        return dataclasses.replace(pc, pages_k=pk)

    r2 = races.check_tick_jaxpr(good, (eng.model, pc,
                                       jnp.zeros(2, jnp.int32)),
                                pc, "good_tick")
    assert not r2.errors, _errors(r2)


def test_races_shadow_mode_real_tick_and_seeded_stray():
    """Shadow-page dynamic mode: snapshot the pool around a REAL
    2-token decode tick — changed pages ⊆ expected write set; then
    seed a stray write into the 'after' snapshot and the checker must
    name the violated page."""
    from triton_dist_tpu.models.scheduler import PagedDecodeSlots, Request
    cfg, eng = _tiny_engine()
    slots = PagedDecodeSlots(eng, 2, page=8, prefix_cache=False)
    rng = np.random.RandomState(0)
    for i in range(2):
        slots.admit(i, Request(
            rid=i, ids=rng.randint(0, cfg.vocab_size, size=(5 + i,)
                                   ).astype(np.int32), gen_len=8))
    live = races.check_scheduler(slots)
    assert not live.errors, _errors(live)
    before = races.snapshot_pool(slots.cache)
    expected = races.expected_write_pages(slots, steps=2)
    slots.step_chunk(2)
    after = races.snapshot_pool(slots.cache)
    r = races.check_shadow(before, after, expected,
                           trash=slots.cache.trash)
    assert not r.errors, _errors(r)
    # seeded stray: scribble a page outside the expected set
    stray = max(set(range(slots.cache.num_pages)) - expected
                - {slots.cache.trash})
    evil = [a.copy() for a in after]
    evil[0] = evil[0].copy()
    evil[0][stray] = evil[0][stray] + 1.0
    r2 = races.check_shadow(before, evil, expected,
                            trash=slots.cache.trash)
    msgs = _errors(r2)
    assert any(f"page {stray}" in m for m in msgs), msgs


def test_races_fork_sharing_legal_and_violation_fires():
    """ISSUE 17: the fork-aware write-exclusivity proof. (a) n KV-fork
    slots mapping the SAME refcount>1 prompt pages read-only is LEGAL
    — check_scheduler over a live n=3 forked scheduler stays clean.
    (b) Seeded violation: mutate one fork's table so its write tile
    resolves to a fork-shared page (bypassing the CoW boundary copy)
    and the checker must fire a 'fork CoW violation' naming the page."""
    import dataclasses
    from triton_dist_tpu.models.scheduler import (ContinuousScheduler,
                                                  Request)
    cfg, eng = _tiny_engine(backend="xla")
    sched = ContinuousScheduler(eng, batch=4, chunk=2, paged=True,
                                page=4)
    sched.submit(Request(rid="F", ids=np.arange(1, 10, dtype=np.int32),
                         gen_len=6, n=3))
    for _ in range(2):
        sched.poll()
    slots = sched.slots
    assert int(slots._is_fork.sum()) == 2, slots._is_fork
    clean = races.check_scheduler(sched)
    assert not clean.errors, _errors(clean)
    # mutation: point a fork's write tile at a page its parent (and
    # sibling) still map — the write the CoW boundary copy exists to
    # prevent
    table = np.asarray(jax.device_get(slots.cache.table)).copy()
    pos = np.asarray(jax.device_get(slots.pos))
    fork = int(np.nonzero(slots._is_fork)[0][0])
    shared_page = int(slots._pages[fork][0])
    table[fork, int(pos[fork]) // slots.page] = shared_page
    slots.cache = dataclasses.replace(slots.cache,
                                      table=jnp.asarray(table))
    r = races.check_scheduler(sched)
    msgs = _errors(r)
    assert any("fork CoW violation" in m and f"page {shared_page}" in m
               for m in msgs), msgs


# ---------------------------------------------------------------------------
# checker 4: hot-loop lint
# ---------------------------------------------------------------------------

def test_hotloop_clean_engine():
    r = hotloop.run()
    assert not r.errors, _errors(r)
    assert len(r.covered) >= 8


def test_hotloop_flags_host_transfer_in_tick():
    def bad_tick(x):
        y = jax.pure_callback(
            lambda v: np.asarray(v) + 1,
            jax.ShapeDtypeStruct(x.shape, x.dtype), x)
        return y * 2

    r = Report("hotloop")
    hotloop.check_host_transfers(bad_tick, (jnp.zeros((4,)),), {},
                                 "bad_tick", r)
    msgs = _errors(r)
    assert any("host transfer" in m and "callback" in m
               for m in msgs), msgs


def test_hotloop_flags_trace_churn():
    counter = [0]

    def churny(x):
        counter[0] += 1
        return x + float(counter[0])   # baked literal differs per trace

    r = Report("hotloop")
    hotloop.check_trace_determinism(churny, (jnp.zeros((4,)),), {},
                                    "churny", r)
    msgs = _errors(r)
    assert any("recompile-key churn" in m for m in msgs), msgs


def test_hotloop_program_cache_identity():
    r = Report("hotloop")
    hotloop.check_program_cache_identity(r)
    assert not r.errors, _errors(r)


# ---------------------------------------------------------------------------
# satellite checker: dead-code lint
# ---------------------------------------------------------------------------

def test_deadcode_clean_package():
    r = deadcode.run()
    assert not r.findings, [f.format() for f in r.findings]


def test_deadcode_fixtures_fire():
    src = (
        "import os\n"
        "import sys  # noqa: F401\n"
        "from json import dumps\n"
        "def dumps():\n"
        "    return 1\n"
        "def dead():\n"
        "    return 2\n"
        "    x = 3\n"
        "def dead():\n"
        "    return 4\n"
    )
    r = deadcode.check_source(src, "fixture.py")
    msgs = [f.format() for f in r.findings]
    assert any("unused import 'os'" in m for m in msgs), msgs
    assert not any("'sys'" in m for m in msgs), msgs       # noqa respected
    assert any("shadows the import" in m for m in msgs), msgs
    assert any("duplicate top-level definition" in m for m in msgs), msgs
    assert any("unreachable code" in m for m in msgs), msgs
    assert all("fixture.py:" in m for m in msgs), msgs


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def test_cli_rejects_unknown_checker():
    from triton_dist_tpu.analysis.__main__ import main
    with pytest.raises(SystemExit):
        main(["not_a_checker"])


def test_cli_deadcode_exits_zero():
    from triton_dist_tpu.analysis.__main__ import main
    assert main(["deadcode"]) == 0
