"""Selective state-space (Mamba-1) kernels: the prefill scan and the
one-token state update of decode, each with its plain-XLA oracle.

The recurrence, per channel c < E and state n < N (arXiv:2312.00752,
the S6 layer; the layer Phi-4-mini-flash's SambaY decoder alternates
with attention, arXiv:2507.06607):

    s_t = exp(dt_t[c] * A[n, c]) * s_{t-1} + (dt_t[c] * x_t[c]) * B_t[n]
    y_t[c] = sum_n s_t[n, c] * C_t[n] + D[c] * x_t[c]

Layout: the state is [N, E] float32 — channels on the lanes, the N = 16
states on the sublanes (two f32 tiles) — and `A` is handed in already
transposed to it. B_t and C_t enter as [.., N, 1] columns, which
broadcast along the lanes; x_t and dt_t as [1, E] rows, which broadcast
along the sublanes; y_t is one sublane reduction. Nothing is a matmul:
the work is the VPU's and the EUP's (one exp per state element), and
decode is bound by reading and writing the state.

`selective_scan` (prefill) is CHUNKED: the grid walks blocks of
`block_t` positions with the state resident in VMEM across them, so a
2,048-token prompt is 16 grid steps a channel block, not 2,048 XLA
loop iterations. `valid_len` freezes the state past the prompt's real
length (dt is zeroed there: exp(0) = 1 and the input term vanishes), so
a prompt padded to its bucket leaves the state of its last real token.

`ssm_step` (decode) updates every slot's state in place (the input is
aliased to the output) and leaves a slot whose `keep` is 0 exactly as
it was.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_dist_tpu.runtime import interpret_mode

_ROWS = 8            # positions per aligned f32 tile of x / dt / y


# ----------------------------------------------------------------------
# oracles
# ----------------------------------------------------------------------

def selective_scan_ref(x, dt, Bm, Cm, A, D, s0, valid_len=None):
    """x, dt [S, E]; Bm, Cm [S, N]; A [N, E]; D [E]; s0 [N, E]; all
    float32. Returns (y [S, E], s_last [N, E]); positions at or past
    `valid_len` leave the state alone."""
    S = x.shape[0]
    if valid_len is not None:
        dt = jnp.where((jnp.arange(S) < valid_len)[:, None], dt, 0.0)

    def step(s, inp):
        x_t, dt_t, b_t, c_t = inp
        s = jnp.exp(dt_t[None, :] * A) * s \
            + (dt_t * x_t)[None, :] * b_t[:, None]
        return s, jnp.sum(s * c_t[:, None], axis=0) + D * x_t

    s_last, y = jax.lax.scan(step, s0, (x, dt, Bm, Cm))
    return y, s_last


def ssm_step_ref(x, dt, Bm, Cm, A, D, s, keep):
    """One token for every slot: x, dt [B, E]; Bm, Cm [B, N]; s
    [B, N, E]; keep [B] bool. Returns (y [B, E], s_new [B, N, E])."""
    s_new = jnp.exp(dt[:, None, :] * A[None]) * s \
        + (dt * x)[:, None, :] * Bm[:, :, None]
    y = jnp.sum(s_new * Cm[:, :, None], axis=1) + D[None] * x
    return y, jnp.where(keep[:, None, None], s_new, s)


# ----------------------------------------------------------------------
# prefill: the chunked scan
# ----------------------------------------------------------------------

def _scan_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, d_ref, s0_ref,
                 y_ref, sl_ref, s_scr):
    t = pl.program_id(1)
    bt = x_ref.shape[0]

    @pl.when(t == 0)
    def _init():
        s_scr[...] = s0_ref[...]

    A = a_ref[...]                       # [N, be]
    Dk = d_ref[...]                      # [1, be]

    def tile(i, s):
        base = pl.multiple_of(i * _ROWS, _ROWS)
        x8 = x_ref[pl.ds(base, _ROWS), :]
        dt8 = dt_ref[pl.ds(base, _ROWS), :]
        ys = []
        for j in range(_ROWS):
            x_t, dt_t = x8[j:j + 1, :], dt8[j:j + 1, :]
            s = jnp.exp(dt_t * A) * s + (dt_t * x_t) * b_ref[base + j]
            ys.append(jnp.sum(s * c_ref[base + j], axis=0, keepdims=True)
                      + Dk * x_t)
        y_ref[pl.ds(base, _ROWS), :] = jnp.concatenate(ys, axis=0)
        return s

    s = jax.lax.fori_loop(0, bt // _ROWS, tile, s_scr[...])
    s_scr[...] = s

    @pl.when(t == pl.num_programs(1) - 1)
    def _done():
        sl_ref[...] = s


def _block(n: int, target: int, unit: int) -> int:
    """Largest multiple of `unit` that divides n and is <= target (n
    itself where none does: a block equal to the dimension is legal)."""
    b = min(target, n) // unit * unit
    while b >= unit and n % b:
        b -= unit
    return b if b >= unit else n


def selective_scan(x, dt, Bm, Cm, A, D, s0, valid_len=None, *,
                   block_e: int = 512, block_t: int = 128):
    """Pallas twin of selective_scan_ref (same contract). S must be a
    multiple of 8 (the admission's pad bucket is)."""
    S, E = x.shape
    N = A.shape[0]
    assert S % _ROWS == 0, "the scan walks aligned tiles of 8 positions"
    if valid_len is not None:
        dt = jnp.where((jnp.arange(S) < valid_len)[:, None], dt, 0.0)
    be = _block(E, block_e, 128)
    bt = _block(S, block_t, _ROWS)
    row = pl.BlockSpec((bt, be), lambda e, t: (t, e))
    col = pl.BlockSpec((bt, N, 1), lambda e, t: (t, 0, 0))
    per_e = pl.BlockSpec((N, be), lambda e, t: (0, e))
    y, s_last = pl.pallas_call(
        _scan_kernel,
        grid=(E // be, S // bt),
        in_specs=[row, row, col, col, per_e,
                  pl.BlockSpec((1, be), lambda e, t: (0, e)), per_e],
        out_specs=(row, per_e),
        out_shape=(jax.ShapeDtypeStruct((S, E), jnp.float32),
                   jax.ShapeDtypeStruct((N, E), jnp.float32)),
        scratch_shapes=[pltpu.VMEM((N, be), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret_mode(),
        name="ssm_scan",
    )(x, dt, Bm[:, :, None], Cm[:, :, None], A, D[None, :], s0)
    return y, s_last


# ----------------------------------------------------------------------
# decode: one token, every slot
# ----------------------------------------------------------------------

def _step_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, d_ref, k_ref, s_ref,
                 y_ref, so_ref):
    A = a_ref[...]
    Dk = d_ref[...]
    x, dt = x_ref[...], dt_ref[...]
    for j in range(x.shape[0]):
        x_t, dt_t = x[j:j + 1, :], dt[j:j + 1, :]
        s = s_ref[j]
        s_new = jnp.exp(dt_t * A) * s + (dt_t * x_t) * b_ref[j]
        y_ref[j:j + 1, :] = (jnp.sum(s_new * c_ref[j], axis=0,
                                     keepdims=True) + Dk * x_t)
        so_ref[j] = jnp.where(k_ref[j] > 0, s_new, s)


def ssm_step(x, dt, Bm, Cm, A, D, s, keep, *, block_e: int = 1024):
    """Pallas twin of ssm_step_ref; `s` is updated in place."""
    B, E = x.shape
    N = A.shape[0]
    be = _block(E, block_e, 128)
    bb = _ROWS if B % _ROWS == 0 else B
    row = pl.BlockSpec((bb, be), lambda b, e: (b, e))
    col = pl.BlockSpec((bb, N, 1), lambda b, e: (b, 0, 0))
    per_e = pl.BlockSpec((N, be), lambda b, e: (0, e))
    st = pl.BlockSpec((bb, N, be), lambda b, e: (b, 0, e))
    y, s_new = pl.pallas_call(
        _step_kernel,
        grid=(B // bb, E // be),
        in_specs=[row, row, col, col, per_e,
                  pl.BlockSpec((1, be), lambda b, e: (0, e)),
                  pl.BlockSpec((bb, 1, 1), lambda b, e: (b, 0, 0)), st],
        out_specs=(row, st),
        out_shape=(jax.ShapeDtypeStruct((B, E), jnp.float32),
                   jax.ShapeDtypeStruct((B, N, E), jnp.float32)),
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret_mode(),
        name="ssm_step",
    )(x, dt, Bm[:, :, None], Cm[:, :, None], A, D[None, :],
      keep.astype(jnp.float32)[:, None, None], s)
    return y, s_new


def by_mode(mode: str):
    """(scan, step) for an engine backend string: the oracles under
    "xla", the kernels under every other."""
    if mode == "xla":
        return selective_scan_ref, ssm_step_ref
    return selective_scan, ssm_step
