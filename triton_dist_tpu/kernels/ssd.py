"""Mamba-2 state-space (SSD) kernels: the chunked prefill scan and the
one-token state update of decode, each with its plain-XLA oracle.

The recurrence, per head m < H with a SCALAR decay a head and B and C
shared by the H / G heads of a group g = m // (H / G) (arXiv:2405.21060,
"Transformers are SSMs", the layer Falcon-H1 runs beside attention):

    S_t = exp(dt_t[m] A[m]) S_{t-1} + dt_t[m] B_t[g] x_t[m]^T
    y_t[m] = S_t^T C_t[g] + D[m] x_t[m]

x_t[m] is the head's P channels, B_t[g] and C_t[g] the group's N
states, S [N, P] float32. Layout: the state plane is [.., H, N, P] —
the head's channels on the lanes, its N states on the sublanes (the
published cache holds [heads, head_dim, state]; this is its transpose,
so that x and y are lane rows as in kernels/ssm.py) — and B and C enter
as [N, 1] columns, which broadcast along the lanes.

`ssd_step` (decode) updates every slot's state in place (the input is
aliased to the output), blocks over heads so that VMEM holds a few
[N, P] tiles, and leaves a slot whose `keep` is 0 exactly as it was. Its
work is the VPU's (5 FLOPs a state element) and it is bound by reading
and writing the state.

`ssd_scan` (prefill) is the chunked MATRIX form: chunks of `chunk`
positions walk in order with the state resident in VMEM, and within a
chunk of positions t, s (Acs the chunk's running sum of dt A):

    y[t]  = sum_{s<=t} exp(Acs_t - Acs_s) (C_t . B_s) dt_s x_s
            + exp(Acs_t) S^T C_t                     (MXU products)
    S    <- exp(Acs_last) S + sum_s exp(Acs_last - Acs_s) dt_s B_s x_s^T

(kernels/gdn.py's layout, without the delta rule). `valid_len` freezes
the state past the prompt's real length (dt is zeroed there: the decay
is exp(0) = 1 and the input term vanishes), so a prompt padded to its
bucket leaves the state of its last real token.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_dist_tpu.runtime import interpret_mode

_HI = jax.lax.Precision.HIGHEST


def _expand(v, H):
    """[.., G, N] group rows -> [.., H, N] head rows."""
    return jnp.repeat(v, H // v.shape[-2], axis=-2)


# ----------------------------------------------------------------------
# oracles
# ----------------------------------------------------------------------

def ssd_scan_ref(x, dt, Bm, Cm, A, D, s0, valid_len=None):
    """x [S, H, P]; dt [S, H] (after the softplus); Bm, Cm [S, G, N];
    A, D [H]; s0 [H, N, P]; all float32. The sequential recurrence, one
    position at a time. Returns (y [S, H, P], s_last [H, N, P]);
    positions at or past `valid_len` leave the state alone."""
    S, H, _ = x.shape
    if valid_len is not None:
        dt = jnp.where((jnp.arange(S) < valid_len)[:, None], dt, 0.0)

    def step(s, inp):
        x_t, dt_t, b_t, c_t = inp
        b_t, c_t = _expand(b_t, H), _expand(c_t, H)          # [H, N]
        s = jnp.exp(dt_t * A)[:, None, None] * s \
            + (dt_t[:, None, None] * b_t[:, :, None]) * x_t[:, None, :]
        return s, jnp.sum(s * c_t[:, :, None], axis=1) + D[:, None] * x_t

    s_last, y = jax.lax.scan(step, s0, (x, dt, Bm, Cm))
    return y, s_last


def ssd_step_ref(x, dt, Bm, Cm, A, D, s, keep):
    """One token for every slot: x [B, H, P]; dt [B, H]; Bm, Cm
    [B, G, N]; s [B, H, N, P]; keep [B] bool. Returns (y [B, H, P],
    s_new)."""
    H = x.shape[1]
    b, c = _expand(Bm, H), _expand(Cm, H)                    # [B, H, N]
    s_new = jnp.exp(dt * A)[:, :, None, None] * s \
        + (dt[:, :, None, None] * b[..., None]) * x[:, :, None, :]
    y = jnp.sum(s_new * c[..., None], axis=2) + D[None, :, None] * x
    return y, jnp.where(keep[:, None, None, None], s_new, s)


# ----------------------------------------------------------------------
# decode: one token, every slot
# ----------------------------------------------------------------------

def _bc_rows(Bm, Cm):
    """[B, G, N] x 2 -> [B, G, 8, N]: a group's B row, then its C row,
    then zero rows to a whole sublane tile (the kernel transposes the
    block to two columns)."""
    bc = jnp.stack([Bm, Cm], axis=2)
    return jnp.pad(bc, ((0, 0), (0, 0), (0, 6), (0, 0)))


def _step_kernel(dec_ref, u_ref, bc_ref, k_ref, s_ref, y_ref, so_ref):
    cols = bc_ref[0, 0].T                                # [N, 8]
    b_col, c_col = cols[:, 0:1], cols[:, 1:2]            # [N, 1] each
    keep = k_ref[0] > 0                                  # [1, P]
    dec, u = dec_ref[0], u_ref[0]                        # [bh, P] each
    ys = []
    for j in range(u.shape[0]):
        s = s_ref[0, j]                                  # [N, P]
        s_new = dec[j:j + 1, :] * s + b_col * u[j:j + 1, :]
        ys.append(jnp.sum(s_new * c_col, axis=0, keepdims=True))
        so_ref[0, j] = jnp.where(keep, s_new, s)
    y_ref[0] = jnp.concatenate(ys, axis=0)


def _head_block(H: int, G: int, target: int) -> int:
    """Heads a grid step: a divisor of H / G (a block never straddles
    two groups), at most `target`."""
    per = H // G
    b = min(target, per)
    while per % b:
        b -= 1
    return b


def ssd_step(x, dt, Bm, Cm, A, D, s, keep, *, block_h: int = 8):
    """Pallas twin of ssd_step_ref (same contract); `s` is updated in
    place."""
    B, H, P = x.shape
    G, N = Bm.shape[1], Bm.shape[2]
    bh = _head_block(H, G, block_h)
    # the decay, the input's scale and `keep` as lane rows: a [1, 1]
    # value does not broadcast over a whole tile in the kernel
    dec = jnp.broadcast_to(jnp.exp(dt * A[None])[:, :, None], x.shape)
    u = dt[:, :, None] * x                               # [B, H, P]
    keep = jnp.broadcast_to(keep.astype(jnp.float32)[:, None, None],
                            (B, 1, P))
    per = H // G
    st = pl.BlockSpec((1, bh, N, P), lambda b, h: (b, h, 0, 0))
    row = pl.BlockSpec((1, bh, P), lambda b, h: (b, h, 0))
    y, s_new = pl.pallas_call(
        _step_kernel,
        grid=(B, H // bh),
        in_specs=[row, row,
                  pl.BlockSpec((1, 1, 8, N),
                               lambda b, h: (b, (h * bh) // per, 0, 0)),
                  pl.BlockSpec((1, 1, P), lambda b, h: (b, 0, 0)), st],
        out_specs=(row, st),
        out_shape=(jax.ShapeDtypeStruct((B, H, P), jnp.float32),
                   jax.ShapeDtypeStruct((B, H, N, P), jnp.float32)),
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret_mode(),
        name="ssd_step",
    )(dec, u, _bc_rows(Bm, Cm), keep, s)
    return y + D[None, :, None] * x, s_new


# ----------------------------------------------------------------------
# prefill: the chunked scan
# ----------------------------------------------------------------------

def _scan_kernel(u_ref, acs_ref, dl_ref, c_ref, bt_ref, s0_ref, y_ref,
                 sl_ref, s_scr):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        s_scr[...] = s0_ref[...]

    bh, L = acs_ref.shape
    C = c_ref[0]                                         # [L, N]
    Bt = bt_ref[0]                                       # [N, L]
    G = jnp.dot(C, Bt, precision=_HI,
                preferred_element_type=jnp.float32)      # [L, L]
    acs_rows = acs_ref[...]                              # [bh, L]
    acs_cols = acs_rows.T                                # [L, bh]
    ti = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    causal = si <= ti
    for j in range(bh):
        row, col = acs_rows[j:j + 1, :], acs_cols[:, j:j + 1]
        last = row[:, L - 1:L]                           # [1, 1]
        u = u_ref[j]                                     # [L, P]
        s = s_scr[j]                                     # [N, P]
        m = G * jnp.exp(jnp.where(causal, col - row, -jnp.inf))
        y = jnp.dot(m, u, precision=_HI,
                    preferred_element_type=jnp.float32)
        y = y + jnp.exp(col) * jnp.dot(C, s, precision=_HI,
                                       preferred_element_type=jnp.float32)
        y_ref[j] = y
        w = jnp.exp(last - col) * u                      # [L, P]
        s_scr[j] = dl_ref[j, 0, 0:1, :] * s + jnp.dot(
            Bt, w, precision=_HI, preferred_element_type=jnp.float32)

    @pl.when(t == pl.num_programs(1) - 1)
    def _done():
        sl_ref[...] = s_scr[...]


def ssd_scan(x, dt, Bm, Cm, A, D, s0, valid_len=None, *, chunk: int = 128,
             block_h: int = 8):
    """Pallas twin of ssd_scan_ref (same contract): chunks of `chunk`
    positions (`mamba_chunk_size`), the sequence padded to a whole
    chunk with dt = 0."""
    S, H, P = x.shape
    G, N = Bm.shape[1], Bm.shape[2]
    if valid_len is not None:
        dt = jnp.where((jnp.arange(S) < valid_len)[:, None], dt, 0.0)
    nc = -(-S // chunk)
    pad = nc * chunk - S
    padt = lambda a: jnp.pad(  # noqa: E731
        a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
    x, dt, Bm, Cm = padt(x), padt(dt), padt(Bm), padt(Cm)
    a = (dt * A[None]).reshape(nc, chunk, H)
    acs = jnp.cumsum(a, axis=1)                              # [nc, L, H]
    # a chunk's whole decay, exp(Acs_last), as lane rows a head and
    # chunk: a [1, 1] value does not broadcast over a tile in the kernel
    dl = jnp.broadcast_to(jnp.exp(acs[:, -1].T)[:, :, None, None],
                          (H, nc, 8, P))
    acs = acs.reshape(nc * chunk, H).T                       # [H, S']
    u = jnp.swapaxes(dt[:, :, None] * x, 0, 1)              # [H, S', P]
    c = jnp.swapaxes(Cm, 0, 1)                               # [G, S', N]
    bt = jnp.transpose(Bm, (1, 2, 0))                        # [G, N, S']
    bh = _head_block(H, G, block_h)
    per = H // G
    grp = lambda h: (h * bh) // per  # noqa: E731
    hrow = pl.BlockSpec((bh, chunk, P), lambda h, t: (h, t, 0))
    st = pl.BlockSpec((bh, N, P), lambda h, t: (h, 0, 0))
    y, s_last = pl.pallas_call(
        _scan_kernel,
        grid=(H // bh, nc),
        in_specs=[hrow,
                  pl.BlockSpec((bh, chunk), lambda h, t: (h, t)),
                  pl.BlockSpec((bh, 1, 8, P), lambda h, t: (h, t, 0, 0)),
                  pl.BlockSpec((1, chunk, N), lambda h, t: (grp(h), t, 0)),
                  pl.BlockSpec((1, N, chunk), lambda h, t: (grp(h), 0, t)),
                  st],
        out_specs=(hrow, st),
        out_shape=(jax.ShapeDtypeStruct((H, nc * chunk, P), jnp.float32),
                   jax.ShapeDtypeStruct((H, N, P), jnp.float32)),
        scratch_shapes=[pltpu.VMEM((bh, N, P), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret_mode(),
        name="ssd_scan",
    )(u, acs, dl, c, bt, s0)
    y = jnp.swapaxes(y, 0, 1)[:S]
    return y + D[None, :, None] * x[:S], s_last


def by_mode(mode: str):
    """(scan, step) for an engine backend string: the oracles under
    "xla", the kernels under every other."""
    if mode == "xla":
        return ssd_scan_ref, ssd_step_ref
    return ssd_scan, ssd_step
