"""Grouped GEMM: per-expert matmuls for MoE.

TPU-native re-design of the reference grouped-GEMM library
(`python/triton_dist/kernels/nvidia/group_gemm.py` (1102): nk-const
grouped GEMM, persistent/dynamic variants :251-727).

The reference handles *dynamic* per-expert token counts with
device-side tile scheduling. XLA requires static shapes, and there are
two designs here:

- CAPACITY-BASED (`grouped_gemm`): tokens are pre-grouped into
  [E, C, D] (the jnp sort/scatter in ep_a2a.py plays the role of the
  reference's `moe_ag_scatter_align_block_size` CUDA kernel,
  csrc/lib/moe_utils.cu:61) and the grouped GEMM is a Pallas kernel on
  a (E, C-tiles, F-tiles) grid — every dot lands on the MXU with
  aligned tiles, invalid (padding) rows are computed-then-masked. Its
  work is E x C whatever was routed, and a capacity below the worst
  case drops.
- RAGGED (`group_rows_ragged` + `ragged_grouped_gemm`): the rows are
  laid out sorted by expert, each expert's group padded to the ROW TILE
  only; a tile -> expert map is prefetched as scalars and picks the
  weight panel of every grid step. The buffers are sized for the worst
  case (every row on held experts), the work is not: tiles past the
  last used one skip their dot and fetch nothing. Nothing is ever
  dropped. This is what an expert owner runs in serving
  (layers/ep_moe.py `expert_rows`).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_dist_tpu.runtime import interpret_mode
from triton_dist_tpu.utils import cdiv


def grouped_gemm_ref(x, w):
    """jnp reference: x [E, C, D] @ w [E, D, F] -> [E, C, F]."""
    return jnp.einsum("ecd,edf->ecf", x, w,
                      preferred_element_type=jnp.float32).astype(x.dtype)


def _gg_kernel(x_ref, w_ref, o_ref):
    o_ref[...] = jnp.dot(
        x_ref[0], w_ref[0],
        preferred_element_type=jnp.float32).astype(o_ref.dtype)[None]


def grouped_gemm(x, w, *, block_c=None, block_f=None):
    """Pallas grouped GEMM. x: [E, C, D]; w: [E, D, F] -> [E, C, F].
    Grid (E, C/bc, F/bf); weights stream through VMEM once per (expert,
    F-tile) and are reused across C-tiles by the pallas pipeline.
    Tiling resolves explicit arg > tuned config (tools/sweep,
    the reference's `_get_tiling_size_for_gmm_kernel` role) > 256/512;
    C and F are non-contraction dims, so any tile choice is bitwise-
    identical."""
    E, C, D = x.shape
    F = w.shape[2]
    if block_c is None or block_f is None:
        from triton_dist_tpu.tools.sweep import resolve_config
        cfg = resolve_config("grouped_gemm", (C, F))
        block_c = block_c if block_c is not None else cfg.get("block_c",
                                                              256)
        block_f = block_f if block_f is not None else cfg.get("block_f",
                                                              512)

    def _pick(total, want, align):
        """Largest divisor <= want that satisfies Mosaic's tiling
        (full-dim blocks are exempt); falls back to one full block."""
        b = min(want, total)
        if b >= total:
            return total
        while b >= align:
            if total % b == 0 and b % align == 0:
                return b
            b -= 1
        return total

    bc = _pick(C, block_c, 8)
    bf = _pick(F, block_f, 128)
    grid = (E, cdiv(C, bc), cdiv(F, bf))
    return pl.pallas_call(
        _gg_kernel,
        out_shape=jax.ShapeDtypeStruct((E, C, F), x.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bc, D), lambda e, i, j: (e, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, D, bf), lambda e, i, j: (e, 0, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, bc, bf), lambda e, i, j: (e, i, j),
                               memory_space=pltpu.VMEM),
        interpret=interpret_mode(),
    )(x, w)


# ----------------------------------------------------------------------
# ragged: groups padded to the row tile, work proportional to the rows
# ----------------------------------------------------------------------

@dataclasses.dataclass
class RaggedGroups:
    """Where each work row sits in the padded-sorted layout of
    `group_rows_ragged` (arrays are traced values; rows and block_m
    static)."""
    dest: jax.Array        # [R] row of the layout (== rows: no group)
    src: jax.Array         # [rows] work row held there, -1 = padding
    tile_group: jax.Array  # [rows // block_m] group of each row tile
    n_used: jax.Array      # [1] row tiles that hold a row
    rows: int
    block_m: int


def ragged_block_m(R: int) -> int:
    """Row tile by the number of work rows: a decode tick's few rows an
    expert pad to 32, a prompt's to 128 (fewer, fuller tiles)."""
    return 32 if R <= 4096 else 128


def group_rows_ragged(gid, num_groups: int, block_m: int) -> RaggedGroups:
    """gid [R] int32: the group (local expert) of each work row,
    `num_groups` for a row that belongs to none. Stable within a group.
    The layout has rows = R + num_groups * (block_m - 1) rounded up to
    the tile: enough for any routing, so nothing is dropped."""
    R = gid.shape[0]
    G, bm = num_groups, block_m
    rows = -(-(R + G * (bm - 1)) // bm) * bm
    onehot = (gid[:, None] == jnp.arange(G)).astype(jnp.int32)   # [R, G]
    rank = jnp.sum((jnp.cumsum(onehot, axis=0) - 1) * onehot, axis=1)
    padded = -(-jnp.sum(onehot, axis=0) // bm) * bm              # [G]
    ends = jnp.cumsum(padded)
    starts = ends - padded
    valid = gid < G
    dest = jnp.where(valid, starts[jnp.minimum(gid, G - 1)] + rank, rows)
    src = jnp.full((rows,), -1, jnp.int32).at[dest].set(
        jnp.arange(R, dtype=jnp.int32), mode="drop")
    n_used = ends[-1] // bm
    tiles = jnp.arange(rows // bm)
    at = jnp.minimum(tiles, jnp.maximum(n_used - 1, 0))
    tile_group = jnp.minimum(
        jnp.searchsorted(ends, at * bm, side="right"), G - 1)
    return RaggedGroups(dest=dest.astype(jnp.int32), src=src,
                        tile_group=tile_group.astype(jnp.int32),
                        n_used=n_used.astype(jnp.int32)[None],
                        rows=rows, block_m=bm)


def _ragged_kernel(swiglu: bool, tg_ref, nu_ref, x_ref, *refs):
    o_ref = refs[-1]

    @pl.when(pl.program_id(1) < nu_ref[0])
    def _():
        x = x_ref[...]
        if swiglu:
            g = jnp.dot(x, refs[0][0], preferred_element_type=jnp.float32)
            u = jnp.dot(x, refs[1][0], preferred_element_type=jnp.float32)
            o_ref[...] = (g * jax.lax.logistic(g) * u).astype(o_ref.dtype)
        else:
            o_ref[...] = jnp.dot(
                x, refs[0][0],
                preferred_element_type=jnp.float32).astype(o_ref.dtype)


def ragged_grouped_gemm_ref(x, w, groups: RaggedGroups, *,
                            swiglu: bool = False):
    """jnp reference of `ragged_grouped_gemm` (rows of unused tiles are
    zero here; the kernel leaves them unwritten)."""
    bm = groups.block_m
    wt = w[groups.tile_group]                       # [tiles, K, F]
    xt = x[:groups.rows].reshape(-1, bm, x.shape[1])
    y = jnp.einsum("tmk,tkf->tmf", xt, wt,
                   preferred_element_type=jnp.float32)
    if swiglu:
        g, u = jnp.split(y, 2, axis=-1)
        y = g * jax.lax.logistic(g) * u
    used = (jnp.arange(xt.shape[0]) < groups.n_used[0])[:, None, None]
    y = jnp.where(used, y, 0.0).astype(x.dtype).reshape(groups.rows, -1)
    return jnp.pad(y, ((0, bm), (0, 0)))


def ragged_grouped_gemm(x, w, groups: RaggedGroups, *,
                        swiglu: bool = False, block_f=None):
    """x [>= rows, K] in the layout of `groups`; w [G, K, F] ->
    [rows + block_m, F] (the last tile is where skipped steps point
    their output block: never read). swiglu=True takes w packed
    [gate | up] and returns silu(x gate) * (x up), [rows + block_m,
    F / 2], the activation applied to the float32 products.

    Grid (F-blocks, row tiles), tiles innermost: consecutive tiles of
    one expert name the same weight block, which the pipeline then does
    not fetch again, so a panel is read once per F-block whatever the
    number of its rows; tiles past `n_used` repeat the last used tile's
    blocks and skip the dot."""
    rows, bm = groups.rows, groups.block_m
    G, K, F = w.shape
    Fo = F // 2 if swiglu else F
    if block_f is None:
        # about 8 MB of weight blocks in flight a step
        block_f = max(128, (4 << 20) // (K * w.dtype.itemsize
                                         * (2 if swiglu else 1))
                      // 128 * 128)
    bf = min(block_f, Fo)
    while Fo % bf:
        bf -= 128 if bf > 128 else 1
    nj, nt = Fo // bf, rows // bm

    def x_map(j, i, tg, nu):
        return (jnp.minimum(i, jnp.maximum(nu[0] - 1, 0)), 0)

    def w_map(off):
        return lambda j, i, tg, nu: (tg[i], 0, j + off)

    def o_map(j, i, tg, nu):
        return (jnp.where(i < nu[0], i, nt), j)

    w_specs = [pl.BlockSpec((1, K, bf), w_map(0))]
    if swiglu:
        w_specs.append(pl.BlockSpec((1, K, bf), w_map(nj)))
    return pl.pallas_call(
        functools.partial(_ragged_kernel, swiglu),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(nj, nt),
            in_specs=[pl.BlockSpec((bm, K), x_map)] + w_specs,
            out_specs=pl.BlockSpec((bm, bf), o_map)),
        out_shape=jax.ShapeDtypeStruct((rows + bm, Fo), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=64 << 20),
        interpret=interpret_mode(),
        name="moe_gmm",     # the trace's `%moe_gmm.N` events (PERF.md §3)
    )(groups.tile_group, groups.n_used, x, *([w, w] if swiglu else [w]))
