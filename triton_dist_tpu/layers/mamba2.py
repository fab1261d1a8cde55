"""The Mamba-2 mixer (Falcon-H1's `FalconH1Mixer`) on one chip: input
projection, causal depthwise convolution, the SSD recurrence
(kernels/ssd.py), the grouped gated RMSNorm and the output projection.

    p        = W_in u                  (z | x | B | C | dt: Ei, Ei, G N,
                                        G N, H columns)
    xBC      = silu(conv_K(x | B | C) + b_conv)   over Ei + 2 G N channels
    dt       = softplus(dt + dt_bias); A = -exp(A_log)       a head
    y        = SSD(x, dt, A, B, C) + D x                     (H heads of P)
    out      = W_out RMSNorm_G(y * silu(z))   normalised over each of the
                                              G groups of Ei / G channels

The published multipliers around it (`ssm_in_multiplier` on u, the five
`ssm_multipliers` on p's segments, `ssm_out_multiplier` on out) are
folded into W_in's columns and W_out at construction (`init`), which is
exact in real arithmetic.

A slot's state is two float32 planes: the last K - 1 inputs of the
convolution [K - 1, Ei + 2 G N] and the SSD state [H, N, P] (the head's
channels on the lanes, kernels/ssd.py). `decode` advances both for live
slots only; `prefill` returns them as a prompt's last real token leaves
them.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from triton_dist_tpu.kernels import ssd
from triton_dist_tpu.kernels.quant import qmm


@jax.jit
def fold(w, m):
    """w * m (m a scalar, or one a column) in float32, back in w's
    dtype: a multiplier folded into the weight it follows, in one fused
    pass with no float32 copy of w."""
    return (w.astype(jnp.float32) * m).astype(w.dtype)


def gated_group_norm(y, z, weight, groups: int, eps: float):
    """`mamba_rms_norm` with `norm_before_gate` false: y * silu(z), then
    an RMSNorm over each of `groups` equal slices of the last axis, one
    weight over the whole axis. float32."""
    g = y * jax.nn.silu(z)
    shp = g.shape
    g = g.reshape(shp[:-1] + (groups, shp[-1] // groups))
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    return g.reshape(shp) * weight.astype(jnp.float32)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class Mamba2:
    w_in: jax.Array          # [D, 2 Ei + 2 G N + H], multipliers folded
    conv_w: jax.Array        # [K, Ei + 2 G N] float32
    conv_b: jax.Array        # [Ei + 2 G N] float32
    dt_bias: jax.Array       # [H] float32
    A: jax.Array             # [H] float32, -exp(A_log)
    D: jax.Array             # [H] float32
    norm: jax.Array          # [Ei]
    w_out: jax.Array         # [Ei, D], `ssm_out_multiplier` folded
    n_heads: int = dataclasses.field(metadata=dict(static=True))
    head_dim: int = dataclasses.field(metadata=dict(static=True))
    n_groups: int = dataclasses.field(metadata=dict(static=True))
    d_state: int = dataclasses.field(metadata=dict(static=True))
    chunk: int = dataclasses.field(metadata=dict(static=True))
    eps: float = dataclasses.field(metadata=dict(static=True))

    @staticmethod
    def init(w_in, conv_w, conv_b, dt_bias, A_log, D, norm, w_out, *,
             n_heads: int, head_dim: int, n_groups: int, d_state: int,
             chunk: int, eps: float, in_mult: float = 1.0,
             mup=(1.0,) * 5, out_mult: float = 1.0) -> "Mamba2":
        """From the published tensors (`in_proj` as [D, n_proj], the
        depthwise `conv1d` as [K, channels]) and multipliers."""
        Ei = n_heads * head_dim
        GN = n_groups * d_state
        seg = jnp.concatenate([jnp.full((n,), m, jnp.float32) for n, m in
                               zip((Ei, Ei, GN, GN, n_heads), mup)])
        f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
        return Mamba2(
            w_in=fold(w_in, in_mult * seg), conv_w=f32(conv_w),
            conv_b=f32(conv_b), dt_bias=f32(dt_bias),
            A=-jnp.exp(f32(A_log)), D=f32(D), norm=jnp.asarray(norm),
            w_out=fold(w_out, jnp.float32(out_mult)), n_heads=n_heads,
            head_dim=head_dim, n_groups=n_groups, d_state=d_state,
            chunk=chunk, eps=eps)

    @property
    def d_inner(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def conv_channels(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.d_state

    def _project(self, u):
        """u [M, D] -> (z [M, Ei], xBC [M, C], dt [M, H]) float32."""
        p = qmm(u, self.w_in, preferred_element_type=jnp.float32)
        Ei, C = self.d_inner, self.conv_channels
        return p[:, :Ei], p[:, Ei:Ei + C], p[:, Ei + C:]

    def _ssm_inputs(self, xc, dt):
        """The convolution's output [M, C] and the raw dt [M, H] ->
        (x [M, H, P], dt [M, H], B, C [M, G, N])."""
        M = xc.shape[0]
        Ei, GN = self.d_inner, self.n_groups * self.d_state
        x = xc[:, :Ei].reshape(M, self.n_heads, self.head_dim)
        Bm = xc[:, Ei:Ei + GN].reshape(M, self.n_groups, self.d_state)
        Cm = xc[:, Ei + GN:].reshape(M, self.n_groups, self.d_state)
        return x, jax.nn.softplus(dt + self.dt_bias), Bm, Cm

    def _out(self, y, z, dtype):
        g = gated_group_norm(y.reshape(y.shape[0], -1), z, self.norm,
                             self.n_groups, self.eps)
        return qmm(g.astype(dtype), self.w_out)

    def decode(self, u, conv, state, live, mode: str):
        """One token a slot: u [B, D]; conv [B, K - 1, C], state
        [B, H, N, P] float32; live [B]. Returns (out [B, D], conv,
        state): the planes of a slot that is not live are left as they
        were."""
        _, step = ssd.by_mode(mode)
        z, xbc, dt = self._project(u)
        taps = jnp.concatenate([conv, xbc[:, None]], axis=1)
        xc = jax.nn.silu(self.conv_b + jnp.sum(self.conv_w[None] * taps,
                                               axis=1))
        x, dt, Bm, Cm = self._ssm_inputs(xc, dt)
        y, state = step(x, dt, Bm, Cm, self.A, self.D, state, live)
        conv = jnp.where(live[:, None, None], taps[:, 1:], conv)
        return self._out(y, z, u.dtype), conv, state

    def prefill(self, u, n, mode: str):
        """A prompt u [P, D] (its bucket: rows at or past `n` are
        padding). Returns (out [P, D], the conv plane [K - 1, C] and the
        SSD state [H, N, P] that the prompt's last real token leaves)."""
        scan, _ = ssd.by_mode(mode)
        P_ = u.shape[0]
        K = self.conv_w.shape[0]
        z, xbc, dt = self._project(u)
        xp = jnp.pad(xbc, ((K - 1, 0), (0, 0)))
        xc = jax.nn.silu(self.conv_b + sum(
            self.conv_w[k] * xp[k:k + P_] for k in range(K)))
        x, dt, Bm, Cm = self._ssm_inputs(xc, dt)
        s0 = jnp.zeros((self.n_heads, self.d_state, self.head_dim),
                       jnp.float32)
        if mode == "xla":
            y, s_last = scan(x, dt, Bm, Cm, self.A, self.D, s0, n)
        else:
            y, s_last = scan(x, dt, Bm, Cm, self.A, self.D, s0, n,
                             chunk=self.chunk)
        # positions n-K+1 .. n-1 sit at xp[n .. n+K-2]
        tail = jax.lax.dynamic_slice_in_dim(xp, n, K - 1, 0)
        return self._out(y, z, u.dtype), tail, s_last
