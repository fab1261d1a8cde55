"""Shared layer math: RMSNorm, RoPE, TP weight packing.

Reference analogs: RoPE at layers/nvidia/tp_attn.py:165, weight sharding
`shard_local` at layers/nvidia/tp_mlp.py:38 (torch chunk per rank). Here
sharding is declarative (NamedSharding) and packing is a host-side array
transform.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def rms_norm(x, weight, eps: float = 1e-6):
    """RMSNorm in f32 accumulation (Qwen3-style). The result keeps x's
    dtype: an f32 weight must not promote the activation — a bf16
    activation silently becoming f32 here used to cascade into
    full-KV-cache dtype converts per layer per decode step (55% of the
    step time on the profile)."""
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    out = x32 * jax.lax.rsqrt(var + eps) * weight.astype(jnp.float32)
    return out.astype(dt)


def precompute_rope(head_dim: int, max_seq: int, theta: float = 1e6):
    """cos/sin tables [max_seq, head_dim//2] (Qwen3 uses theta=1e6)."""
    inv = 1.0 / (theta ** (np.arange(0, head_dim, 2) / head_dim))
    t = np.arange(max_seq)
    freqs = np.outer(t, inv)
    return (jnp.asarray(np.cos(freqs), dtype=jnp.float32),
            jnp.asarray(np.sin(freqs), dtype=jnp.float32))


def rope_rows(cos, sin, positions, sections=()):
    """The tables' rows (c, s) [T, D/2] at `positions`: [T] int32, one
    position a token, or [3, T] (time, height, width: multi-section
    rotary, the D/2 frequency pairs split by `sections`, e.g.
    (16, 24, 24): pairs 0-15 take their angle from component 0, 16-39
    from component 1, 40-63 from component 2; a text token has all
    three equal and reads what the one-position form reads)."""
    positions = jnp.asarray(positions)
    if positions.ndim == 1:
        return cos[positions], sin[positions]
    assert positions.shape[0] == len(sections) \
        and sum(sections) == cos.shape[-1], (positions.shape, sections)
    comp = np.repeat(np.arange(len(sections)), sections)     # [D/2]
    at = positions[comp].T                                   # [T, D/2]
    pair = jnp.arange(cos.shape[-1])
    return cos[at, pair], sin[at, pair]


def rotate_rows(x, c, s):
    """x [M, H, d] (or [M, d]) rotated by the rows' own angles c, s
    [M, d / 2] (`rope_rows`), dim i paired with dim i + d / 2; float32
    inside, x's dtype out."""
    if x.ndim == 3:
        c, s = c[:, None], s[:, None]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s],
                           axis=-1).astype(x.dtype)


def apply_rope(x, cos, sin, positions, sections=()):
    """Rotate half-pairs: x [..., S, H, D]; cos/sin [max_seq, D/2];
    positions [S], or [3, S] with `sections` (`rope_rows`) (ref:
    tp_attn.py:165 applies the same rotation on the gathered QKV)."""
    c, s = rope_rows(cos, sin, positions, sections)
    c, s = c[:, None, :], s[:, None, :]  # [S, 1, D/2]
    x1, x2 = jnp.split(x, 2, axis=-1)
    dt = x.dtype
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [x1f * c - x2f * s, x2f * c + x1f * s], axis=-1).astype(dt)


def apply_rope_slots(x, cos, sin, pos):
    """Per-slot RoPE: x [B, S, H, D]; pos [B] int32 — row b rotates at
    positions pos[b] .. pos[b]+S-1. The continuous-batching decode path
    (models/scheduler.py), where every batch row is a different request
    at a different sequence position."""
    B, S = x.shape[0], x.shape[1]
    p = pos[:, None] + jnp.arange(S)            # [B, S]
    c = cos[p][:, :, None, :]                   # [B, S, 1, D/2]
    s = sin[p][:, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    dt = x.dtype
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [x1f * c - x2f * s, x2f * c + x1f * s], axis=-1).astype(dt)


def shard_cols_packed(mats, n: int):
    """Pack several column-parallel weights into one matrix whose global
    column layout is n per-rank blocks, each the concat of every input's
    rank-slice: [m0_r | m1_r | ...] for rank r.

    This is how gate/up (MLP) and q/k/v (attention) projections fuse into
    ONE ag_gemm while keeping each rank's output slice self-contained
    (reference analog: per-rank torch chunking in shard_local,
    tp_mlp.py:38).
    """
    blocks = []
    for r in range(n):
        for m in mats:
            cols = m.shape[1] // n
            blocks.append(m[:, r * cols:(r + 1) * cols])
    return jnp.concatenate(blocks, axis=1)
