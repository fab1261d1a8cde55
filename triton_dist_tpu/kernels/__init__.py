"""Overlapped kernel library (reference analog: python/triton_dist/kernels/,
SURVEY.md §2.3). Every op follows the shared reference pattern re-designed
for TPU: a dataclass Context created once (holding tile sizes, the mesh
axis, and a collective_id), a producer side expressed as async remote DMAs
over ICI, and a consumer compute loop whose tiles wait on DMA/semaphore
arrival before the MXU touches the data.
"""

from triton_dist_tpu.kernels.allgather import (  # noqa: F401
    AllGatherMethod,
    all_gather,
    get_auto_all_gather_method,
)
from triton_dist_tpu.kernels.allgather_gemm import (  # noqa: F401
    AllGatherGEMMTensorParallelContext,
    create_ag_gemm_context,
    ag_gemm,
)
from triton_dist_tpu.kernels.reduce_scatter import (  # noqa: F401
    ReduceScatterMethod,
    reduce_scatter,
)
from triton_dist_tpu.kernels.gemm_reduce_scatter import (  # noqa: F401
    GEMMReduceScatterTensorParallelContext,
    create_gemm_rs_context,
    gemm_rs,
)
from triton_dist_tpu.kernels.allreduce import (  # noqa: F401
    AllReduceMethod,
    all_reduce,
    get_auto_allreduce_method,
)
from triton_dist_tpu.kernels.gemm_allreduce import (  # noqa: F401
    GemmARContext,
    create_gemm_ar_context,
    gemm_allreduce,
)
from triton_dist_tpu.kernels.flash_attn import (  # noqa: F401
    attention_cached_ref,
    flash_decode,
)
from triton_dist_tpu.kernels.all_to_all import (  # noqa: F401
    all_to_all,
    low_latency_all_to_all,
)
from triton_dist_tpu.kernels.gdn import (  # noqa: F401
    gdn_fwd,
    gdn_fwd_ref,
)
from triton_dist_tpu.kernels.grad import (  # noqa: F401
    ag_gemm_grad,
    gemm_ar_grad,
    gemm_rs_grad,
)
from triton_dist_tpu.kernels.group_gemm import (  # noqa: F401
    grouped_gemm,
    grouped_gemm_ref,
)
from triton_dist_tpu.kernels.swiglu import (  # noqa: F401
    swiglu,
    swiglu_ref,
)
from triton_dist_tpu.kernels.sp_flash_decode import (  # noqa: F401
    kv_cache_scatter,
    sp_flash_decode,
    sp_flash_decode_ref,
)
from triton_dist_tpu.kernels.p2p import (  # noqa: F401
    p2p_shift,
)
from triton_dist_tpu.kernels.two_tier import (  # noqa: F401
    all_gather_2d,
    all_reduce_2d,
    reduce_scatter_2d,
)
from triton_dist_tpu.kernels.sp_attention import (  # noqa: F401
    gemm_all_to_all,
    qkv_gemm_a2a,
    sp_ring_attention,
    sp_ring_attention_ref,
    ulysses_combine,
    ulysses_dispatch,
)


# ---------------------------------------------------------------------------
# Central kernel registry (ISSUE 15): name -> KernelSpec with a canonical
# sample-shape builder, so tdcheck (triton_dist_tpu/analysis/), the kprof
# ablation runner and the perf tools enumerate kernels from ONE place
# instead of ad-hoc imports. Builders are lazy (imports inside) and return
# (fn, args) TRACE-READY at tiny tile-plausible shapes — registry scans
# use jax.make_jaxpr, never execute, so a full scan is seconds.
# ---------------------------------------------------------------------------

import dataclasses as _dataclasses
import functools as _functools
from typing import Callable as _Callable, Optional as _Optional, \
    Tuple as _Tuple


@_dataclasses.dataclass(frozen=True)
class KernelSpec:
    """One registered kernel: how to build a canonical call, and which
    static checks apply to it.

    build(mesh) -> (fn, args): `fn(*args)` is the host-level op at small
    canonical shapes (the builder may derive its own mesh from the given
    one, e.g. the 2-D two-tier ops). protocol: None = not a comm kernel;
    "strict" = the one-sided signal graph must balance exactly
    (analysis/protocol.py); "dynamic" = the kernel uses data-dependent
    arrival counts (dl.dma_wait_dyn) — ordering/barrier checks only.
    inplace: (input_idx, output_idx) pallas-level input_output_aliases
    the trace MUST carry (the contract analyzer flags a registered
    in-place kernel whose donation went missing). vmem_budget overrides
    the analyzer's default per-grid-step VMEM bound (bytes).
    ablation_phases feeds tools/kprof_run.py (the old ad-hoc PHASES).

    tunables (ISSUE 16): the declared tunable config space — a tuple of
    config dicts (every dict the same keys; only SCHEDULE knobs, never
    anything that changes the math: tuned output must stay bitwise
    equal to the default). tools/sweep.py prunes the space with the
    contracts VMEM/divisibility checker, times survivors, and persists
    the winner per (kernel, shape-bucket, chip); the kernel's default
    path consumes it through sweep.resolve_config. tune_dims(*args) ->
    dims tuple maps the builder's args to the bucketing dims — it MUST
    compute the same dims the consuming kernel derives from its own
    arguments (None = shape-generic, stored under the "*" bucket).
    variants: extra builders at shape-bucket-variant shapes, swept in
    addition to the canonical build."""

    name: str
    module: str
    kind: str                                # "comm" | "compute" | "paged"
    build: _Callable
    min_devices: int = 1
    protocol: _Optional[str] = None
    inplace: _Tuple[_Tuple[int, int], ...] = ()
    vmem_budget: _Optional[int] = None
    ablation_phases: _Tuple[str, ...] = ()
    tunables: _Tuple[dict, ...] = ()
    tune_dims: _Optional[_Callable] = None
    variants: _Tuple[_Callable, ...] = ()

    def __post_init__(self):
        # structural validation at REGISTRATION (a typo'd space fails
        # where it was written, not at sweep time): non-empty dicts,
        # uniform keys — the sweep's pruner then rejects a space whose
        # every config fails VMEM/divisibility before timing anything
        keys = None
        for cfg in self.tunables:
            if not isinstance(cfg, dict) or not cfg:
                raise ValueError(
                    f"KernelSpec({self.name}): tunables must be "
                    f"non-empty config dicts, got {cfg!r}")
            if keys is None:
                keys = set(cfg)
            elif set(cfg) != keys:
                raise ValueError(
                    f"KernelSpec({self.name}): tunable configs must "
                    f"share one key set, got {sorted(keys)} vs "
                    f"{sorted(cfg)}")
        if self.variants and not self.tunables:
            raise ValueError(
                f"KernelSpec({self.name}): shape variants without a "
                f"tunables space have nothing to sweep")


def _np_rng(seed=0):
    import numpy as np
    return np.random.RandomState(seed)


def _f32(rng, *shape):
    import jax.numpy as jnp
    return jnp.asarray(rng.randn(*shape), jnp.float32) * 0.1


def _b_allgather(method):
    def build(mesh):
        n = mesh.shape["tp"]
        x = _f32(_np_rng(), 8 * n, 128)
        return (lambda v: all_gather(v, mesh=mesh, axis="tp",
                                     method=method), (x,))
    return build


def _b_reduce_scatter(method):
    def build(mesh):
        n = mesh.shape["tp"]
        x = _f32(_np_rng(), n, 8 * n, 128)
        return (lambda v: reduce_scatter(v, mesh=mesh, axis="tp",
                                         method=method), (x,))
    return build


def _b_allreduce(method):
    def build(mesh):
        n = mesh.shape["tp"]
        x = _f32(_np_rng(), n, 8 * n, 128)
        return (lambda v: all_reduce(v, mesh=mesh, axis="tp",
                                     method=method), (x,))
    return build


def _b_p2p(mesh):
    n = mesh.shape["tp"]
    x = _f32(_np_rng(), n, 8, 128)
    return (lambda v: p2p_shift(v, mesh=mesh, axis="tp"), (x,))


def _b_all_to_all(low_latency):
    def build(mesh):
        n = mesh.shape["tp"]
        x = _f32(_np_rng(1), n, n, 8, 128)
        fn = low_latency_all_to_all if low_latency else all_to_all
        return (lambda v: fn(v, mesh=mesh, axis="tp"), (x,))
    return build


def _b_ag_gemm(mesh):
    from triton_dist_tpu.kernels.allgather_gemm import (
        ag_gemm, create_ag_gemm_context)
    n = mesh.shape["tp"]
    rng = _np_rng(2)
    a = _f32(rng, 8 * n, 128)
    b = _f32(rng, 128, 32 * n)
    ctx = create_ag_gemm_context(mesh)
    return (lambda x, w: ag_gemm(x, w, ctx), (a, b))


def _b_gemm_rs(mesh):
    from triton_dist_tpu.kernels.gemm_reduce_scatter import (
        create_gemm_rs_context, gemm_rs)
    n = mesh.shape["tp"]
    rng = _np_rng(3)
    a = _f32(rng, 8 * n, 128)
    b = _f32(rng, 128, 128)
    ctx = create_gemm_rs_context(mesh)
    return (lambda x, w: gemm_rs(x, w, ctx), (a, b))


def _b_gemm_ar(mesh):
    from triton_dist_tpu.kernels.gemm_allreduce import (
        create_gemm_ar_context, gemm_allreduce)
    rng = _np_rng(4)
    a = _f32(rng, 8, 128)
    b = _f32(rng, 128, 128)
    ctx = create_gemm_ar_context(mesh)
    return (lambda x, w: gemm_allreduce(x, w, ctx), (a, b))


def _b_sp_flash_decode(combine):
    def build(mesh):
        n = mesh.shape["tp"]
        rng = _np_rng(5)
        B, Hq, Hkv, T, d = 1, 4, 2, 16 * n, 128
        import jax.numpy as jnp
        q = _f32(rng, B, 1, Hq, d)
        k = _f32(rng, B, Hkv, T, d)
        v = _f32(rng, B, Hkv, T, d)
        return (lambda q_, k_, v_: sp_flash_decode(
            q_, k_, v_, jnp.int32(T), mesh=mesh, axis="tp",
            combine=combine), (q, k, v))
    return build


def _b_kv_scatter(mesh):
    n = mesh.shape["tp"]
    rng = _np_rng(6)
    B, Hkv, T, d = 1, 2, 16 * n, 128
    cache = _f32(rng, B, Hkv, T, d)
    new = _f32(rng, B, Hkv, T, d)
    return (lambda c, kn: kv_cache_scatter(c, kn, mesh=mesh, axis="tp"),
            (cache, new))


def _b_sp_ring(mode):
    def build(mesh):
        n = mesh.shape["tp"]
        rng = _np_rng(7)
        B, H, S, d = 1, 2, 8 * n, 128
        q = _f32(rng, B, S, H, d)
        k = _f32(rng, B, H, S, d)
        v = _f32(rng, B, H, S, d)
        return (lambda q_, k_, v_: sp_ring_attention(
            q_, k_, v_, mesh=mesh, axis="tp", mode=mode), (q, k, v))
    return build


def _b_ep_dispatch_combine(mesh):
    from triton_dist_tpu.kernels.ep_a2a import (create_ep_a2a_context,
                                                ep_dispatch_combine)
    n = mesh.shape["tp"]
    rng = _np_rng(8)
    T, D, E = 8 * n, 128, 2 * n
    x = _f32(rng, T, D)
    logits = _f32(rng, T, E)
    ctx = create_ep_a2a_context(mesh, axis="tp", num_experts=E,
                                capacity=T)
    return (lambda x_, l_: ep_dispatch_combine(x_, l_, 2, ctx), (x, logits))


def _b_ep_fused(mesh):
    import jax
    from jax.sharding import PartitionSpec as P
    from triton_dist_tpu.kernels.ep_fused import ep_moe_fused_device
    from triton_dist_tpu.runtime import next_collective_id
    n = mesh.shape["tp"]
    rng = _np_rng(9)
    E_loc, cap_e, D, I = 2, 16, 128, 128
    x = _f32(rng, n * E_loc * cap_e * n, D)
    wgu = _f32(rng, E_loc * n, D, 2 * I)
    wd = _f32(rng, E_loc * n, I, D)
    cid = next_collective_id()

    @_functools.partial(jax.shard_map, mesh=mesh,
                        in_specs=(P("tp", None), P("tp", None, None),
                                  P("tp", None, None)),
                        out_specs=P("tp", None, None, None),
                        check_vma=False)
    def _ep(x_loc, wgu_loc, wd_loc):
        return ep_moe_fused_device(x_loc, wgu_loc, wd_loc, n=n,
                                   axis="tp", cap_e=cap_e,
                                   collective_id=cid)

    return (_ep, (x, wgu, wd))


def _b_ag_group_gemm(mesh):
    from triton_dist_tpu.kernels.ag_group_gemm import ag_group_gemm
    n = mesh.shape["tp"]
    rng = _np_rng(10)
    E, capT, D, N = 2, 8 * n, 128, 128
    xe = _f32(rng, E, capT, D)
    we = _f32(rng, E, D, N)
    return (lambda x, w: ag_group_gemm(x, w, mesh=mesh, axis="tp"),
            (xe, we))


def _b_moe_reduce(which):
    def build(mesh):
        from triton_dist_tpu.kernels.moe_reduce_ar import moe_reduce_ar
        from triton_dist_tpu.kernels.moe_reduce_rs import moe_reduce_rs
        n = mesh.shape["tp"]
        rng = _np_rng(11)
        E, capT, F, D = 2, 8 * n, 128, 128
        h = _f32(rng, E, capT, F)
        w2 = _f32(rng, E, F, D)
        fn = moe_reduce_ar if which == "ar" else moe_reduce_rs
        return (lambda h_, w_: fn(h_, w_, mesh=mesh, axis="tp"), (h, w2))
    return build


def _b_two_tier(which):
    def build(mesh):
        import jax
        from triton_dist_tpu.kernels.two_tier import (all_gather_2d,
                                                      all_reduce_2d,
                                                      reduce_scatter_2d)
        devs = list(mesh.devices.ravel())
        mesh2 = jax.make_mesh((2, len(devs) // 2), ("dcn", "tp"),
                              devices=devs)
        n = len(devs)
        rng = _np_rng(12)
        fn = {"ag": all_gather_2d, "rs": reduce_scatter_2d,
              "ar": all_reduce_2d}[which]
        if which == "ag":
            x = _f32(rng, 8 * n, 128)
        else:
            x = _f32(rng, n, 8 * n, 128)
        return (lambda v: fn(v, mesh=mesh2, chip_axis="tp",
                             slice_axis="dcn"), (x,))
    return build


def _b_flash_decode(B=2):
    def build(mesh):
        import jax.numpy as jnp
        rng = _np_rng(13)
        Hq, Hkv, T, d = 4, 2, 256, 128
        q = _f32(rng, B, 1, Hq, d)
        k = _f32(rng, B, Hkv, T, d)
        v = _f32(rng, B, Hkv, T, d)
        return (lambda q_, k_, v_: flash_decode(q_, k_, v_, jnp.int32(T)),
                (q, k, v))
    return build


def _b_flash_decode_paged(partial):
    def build(mesh):
        import jax.numpy as jnp
        import numpy as np
        from triton_dist_tpu.kernels.paged_kv import (
            flash_decode_paged, flash_decode_paged_partial)
        rng = _np_rng(14)
        # page 16 as served: 8 pages make one block of the walk
        B, Hq, Hkv, d, page, maxp = 4, 4, 2, 128, 16, 16
        NP = B * maxp
        q = _f32(rng, B, 1, Hq, d)
        pages = _f32(rng, NP, Hkv, page, d)
        table = jnp.arange(NP, dtype=jnp.int32).reshape(B, maxp)
        kv_lens = jnp.asarray([page * maxp, page, 0, 3 * page + 1],
                              jnp.int32)
        # the table rides as a positional arg so tune_dims can read
        # B off it (the dim block_w legality divides)
        if partial:
            owned = jnp.asarray(
                np.ones((B, maxp), np.int32))
            return (lambda q_, pk, pv, t_: flash_decode_paged_partial(
                q_, pk, pv, t_, kv_lens=kv_lens, tile_owned=owned),
                (q, pages, pages, table))
        return (lambda q_, pk, pv, t_: flash_decode_paged(
            q_, pk, pv, t_, None, kv_lens=kv_lens),
            (q, pages, pages, table))
    return build


def _b_kv_update(mesh):
    import jax.numpy as jnp
    from triton_dist_tpu.kernels.flash_attn import kv_update
    rng = _np_rng(15)
    B, H, T, d, S = 1, 2, 256, 128, 8
    cache = _f32(rng, B, H, T, d)
    new = _f32(rng, B, H, S, d)
    return (lambda c, n_: kv_update(c, n_, jnp.int32(0)), (cache, new))


def _b_grouped_gemm(C=64):
    def build(mesh):
        rng = _np_rng(16)
        x = _f32(rng, 2, C, 128)
        w = _f32(rng, 2, 128, 128)
        return (grouped_gemm, (x, w))
    return build


def _b_swiglu(mesh):
    rng = _np_rng(17)
    return (swiglu, (_f32(rng, 64, 256),))


def _b_gdn(mesh):
    import jax.numpy as jnp
    import numpy as np
    rng = _np_rng(18)
    B, H, T, d = 1, 2, 128, 128
    q = _f32(rng, B, H, T, d)
    k = _f32(rng, B, H, T, d)
    v = _f32(rng, B, H, T, d)
    g = jnp.asarray(-np.abs(rng.rand(B, H, T)) * 0.1, jnp.float32)
    b = jnp.asarray(rng.rand(B, H, T), jnp.float32)
    return (lambda *a: gdn_fwd(*a), (q, k, v, g, b))


def _b_flash_attention(mesh):
    from triton_dist_tpu.kernels.flash_attn_train import flash_attention
    rng = _np_rng(19)
    B, S, Hq, Hkv, d = 1, 128, 2, 2, 128
    q = _f32(rng, B, S, Hq, d)
    k = _f32(rng, B, Hkv, S, d)
    v = _f32(rng, B, Hkv, S, d)
    return (flash_attention, (q, k, v))


# Tunable config spaces (ISSUE 16). SCHEDULE knobs only — every axis
# here retiles a non-contraction dim, regroups streams, or changes
# staging/residency depth, so tuned output stays bitwise equal to the
# default (tests/test_sweep.py asserts it). Deliberately NOT tunable:
# flash block_t (KV tile size regroups the online-softmax updates) and
# ep_fused block_i (splits the down-proj contraction) — both change
# float summation order.
def _grid(key, *vals):
    return tuple({key: v} for v in vals)


_TUNE_FLASH_DECODE = _grid("block_x", 32, 64, 128)
# paged walk: W slots per grid step (the block of pages is fixed,
# paged_kv._KV_TILE)
_TUNE_PAGED = _grid("block_w", 1, 2, 4, 8)
_TUNE_GROUPED_GEMM = ({"block_c": 128, "block_f": 256},
                      {"block_c": 256, "block_f": 512},
                      {"block_c": 256, "block_f": 1024},
                      {"block_c": 512, "block_f": 512})
_TUNE_AG_GEMM = _grid("block_n", 256, 512, 1024, 2048)
_TUNE_COMM_GEMM = _grid("block_n", 256, 512, 1024)
_TUNE_AG_GROUP = tuple({"block_n": bn, "wb_depth": wd}
                       for bn in (256, 512) for wd in (2, 4))
_TUNE_MOE_RS = _grid("wb_depth", 2, 3, 4)
_TUNE_EP_FUSED = _grid("resident_w", True, False)

# bucketing dims, shared convention with the consuming kernel (see
# KernelSpec docstring): flash_decode (X=B*Hkv, T); paged (X=B*Hkv,
# B*Hq, pool positions) — X leads because block_w legality divides
# the slots B = X / Hkv, so the bucket key must separate GQA ratios
# and head counts; grouped_gemm (C, F);
# ag_group_gemm (E, capT, N); moe_reduce_rs (E, capT, D).
# Context-scoped kernels (ag_gemm/gemm_rs/gemm_ar/ep_fused) have no
# shapes at resolution time: tune_dims=None.
_DIMS_FLASH_DECODE = lambda q, k, v: (q.shape[0] * k.shape[1],  # noqa: E731
                                      k.shape[2])
_DIMS_PAGED = lambda q, pk, pv, t: (t.shape[0] * pk.shape[1],   # noqa: E731
                                    q.shape[0] * q.shape[2],
                                    pk.shape[0] * pk.shape[2])
_DIMS_GROUPED = lambda x, w: (x.shape[1], w.shape[2])           # noqa: E731
_DIMS_EXPERT = lambda a, b: (a.shape[0], a.shape[1],            # noqa: E731
                             b.shape[2])


@_functools.lru_cache(maxsize=None)
def kernel_registry() -> dict:
    """The canonical kernel enumeration: name -> KernelSpec."""
    specs = [
        # --- one-sided comm kernels (analysis/protocol.py scope) ---
        KernelSpec("allgather_one_shot", "kernels.allgather", "comm",
                   _b_allgather(AllGatherMethod.ONE_SHOT),
                   min_devices=2, protocol="strict"),
        KernelSpec("allgather_ring", "kernels.allgather", "comm",
                   _b_allgather(AllGatherMethod.RING),
                   min_devices=2, protocol="strict"),
        KernelSpec("reduce_scatter_one_shot", "kernels.reduce_scatter",
                   "comm", _b_reduce_scatter(ReduceScatterMethod.ONE_SHOT),
                   min_devices=2, protocol="strict"),
        KernelSpec("reduce_scatter_ring", "kernels.reduce_scatter",
                   "comm", _b_reduce_scatter(ReduceScatterMethod.RING),
                   min_devices=2, protocol="strict"),
        KernelSpec("allreduce_one_shot", "kernels.allreduce", "comm",
                   _b_allreduce(AllReduceMethod.ONE_SHOT),
                   min_devices=2, protocol="strict"),
        KernelSpec("allreduce_two_shot", "kernels.allreduce", "comm",
                   _b_allreduce(AllReduceMethod.TWO_SHOT),
                   min_devices=2, protocol="strict"),
        KernelSpec("p2p_shift", "kernels.p2p", "comm", _b_p2p,
                   min_devices=2, protocol="strict"),
        KernelSpec("all_to_all", "kernels.all_to_all", "comm",
                   _b_all_to_all(False), min_devices=2,
                   protocol="strict"),
        KernelSpec("low_latency_all_to_all", "kernels.all_to_all",
                   "comm", _b_all_to_all(True), min_devices=2,
                   protocol="strict"),
        KernelSpec("ep_dispatch_combine", "kernels.ep_a2a", "comm",
                   _b_ep_dispatch_combine, min_devices=2,
                   protocol="strict"),
        # predicated: the combine puts sit under pl.when(q != me), and a
        # trace records BOTH branches — exact balance is unknowable
        # statically, so ordering/barrier checks only
        KernelSpec("ep_fused", "kernels.ep_fused", "comm", _b_ep_fused,
                   min_devices=2, protocol="predicated",
                   ablation_phases=("dots", "w_stream", "a_stream",
                                    "stage"),
                   tunables=_TUNE_EP_FUSED),
        KernelSpec("sp_flash_decode_dist", "kernels.sp_flash_decode",
                   "comm", _b_sp_flash_decode("dist"), min_devices=2,
                   protocol="strict"),
        KernelSpec("kv_cache_scatter", "kernels.sp_flash_decode", "comm",
                   _b_kv_scatter, min_devices=2, protocol="dynamic",
                   inplace=((1, 0),)),
        KernelSpec("sp_ring_shmem", "kernels.sp_attention", "comm",
                   _b_sp_ring("ring_shmem"), min_devices=2,
                   protocol="strict"),
        KernelSpec("ag_gemm", "kernels.allgather_gemm", "comm",
                   _b_ag_gemm, min_devices=2, protocol="strict",
                   tunables=_TUNE_AG_GEMM),
        KernelSpec("gemm_rs", "kernels.gemm_reduce_scatter", "comm",
                   _b_gemm_rs, min_devices=2, protocol="strict",
                   tunables=_TUNE_COMM_GEMM),
        KernelSpec("gemm_ar", "kernels.gemm_allreduce", "comm",
                   _b_gemm_ar, min_devices=2, protocol="strict",
                   tunables=_TUNE_COMM_GEMM),
        KernelSpec("ag_group_gemm", "kernels.ag_group_gemm", "comm",
                   _b_ag_group_gemm, min_devices=2, protocol="strict",
                   ablation_phases=("dots", "b_stream", "a_stream",
                                    "writeback"),
                   tunables=_TUNE_AG_GROUP, tune_dims=_DIMS_EXPERT),
        KernelSpec("moe_reduce_rs", "kernels.moe_reduce_rs", "comm",
                   _b_moe_reduce("rs"), min_devices=2, protocol="strict",
                   ablation_phases=("dots", "b_stream", "a_stream",
                                    "writeback", "fold"),
                   tunables=_TUNE_MOE_RS, tune_dims=_DIMS_EXPERT),
        KernelSpec("moe_reduce_ar", "kernels.moe_reduce_ar", "comm",
                   _b_moe_reduce("ar"), min_devices=2, protocol="strict"),
        KernelSpec("all_gather_2d", "kernels.two_tier", "comm",
                   _b_two_tier("ag"), min_devices=4, protocol="strict"),
        KernelSpec("reduce_scatter_2d", "kernels.two_tier", "comm",
                   _b_two_tier("rs"), min_devices=4, protocol="strict"),
        KernelSpec("all_reduce_2d", "kernels.two_tier", "comm",
                   _b_two_tier("ar"), min_devices=4, protocol="strict"),
        # --- single-chip compute / paged kernels ---
        KernelSpec("flash_decode", "kernels.flash_attn", "compute",
                   _b_flash_decode(), tunables=_TUNE_FLASH_DECODE,
                   tune_dims=_DIMS_FLASH_DECODE,
                   variants=(_b_flash_decode(8),)),
        KernelSpec("flash_decode_paged", "kernels.paged_kv", "paged",
                   _b_flash_decode_paged(False), tunables=_TUNE_PAGED,
                   tune_dims=_DIMS_PAGED),
        KernelSpec("flash_decode_paged_partial", "kernels.paged_kv",
                   "paged", _b_flash_decode_paged(True),
                   tunables=_TUNE_PAGED, tune_dims=_DIMS_PAGED),
        KernelSpec("kv_update", "kernels.flash_attn", "compute",
                   _b_kv_update, inplace=((2, 0),)),
        KernelSpec("grouped_gemm", "kernels.group_gemm", "compute",
                   _b_grouped_gemm(), tunables=_TUNE_GROUPED_GEMM,
                   tune_dims=_DIMS_GROUPED,
                   variants=(_b_grouped_gemm(256),)),
        KernelSpec("swiglu", "kernels.swiglu", "compute", _b_swiglu),
        KernelSpec("gdn_fwd", "kernels.gdn", "compute", _b_gdn,
                   ablation_phases=("exps", "solve", "out", "state")),
        KernelSpec("flash_attention", "kernels.flash_attn_train",
                   "compute", _b_flash_attention),
    ]
    return {s.name: s for s in specs}
