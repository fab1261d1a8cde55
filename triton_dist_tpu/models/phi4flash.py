"""Phi-4-mini-flash-reasoning (`model_type: phi4flash`): the SambaY
decoder-hybrid-decoder of arXiv:2507.06607, served through the same
Engine / scheduler / TokenServer path as the Qwen3 families.

Five kinds of layer (`Phi4FlashConfig.kind`), every one followed by a
SwiGLU MLP, LayerNorm before both, no positional encoding, tied head:

  mamba   l even, l <= half   Mamba-1 (kernels/ssm.py); layer `half`
                              also hands on its scan output as memory M
  swa     l odd,  l <  half   differential attention over a window
  full    l = half + 1        differential attention, causal; its K/V
                              are the ONE paged pool of the model
  cross   l odd,  l > half+1  queries only; attends layer half+1's pool
  gmu     l even, l > half    Wout (M * silu(Win u)): no state at all

The equations are written out in benchmark/reference/phi4flash.py, which
the tier-1 tests hold this module to.

DIFFERENTIAL ATTENTION WITHOUT A NEW KERNEL. Heads pair up (2j, 2j+1).
A pair's keys are stored as ONE 2*hd-wide head [k1 | k2] and its values
as [v1 | v2] (a plain reshape of the projection's output), so the pool
has Hkv/2 heads of 128 at the published sizes: the layout
flash_decode_paged and flash_decode already walk. softmax(q1 k1^T) V is
then ordinary attention with the query [q1 | 0], and softmax(q2 k2^T) V
with [0 | q2], at scale hd**-0.5: exact, at twice the QK FLOPs of a walk
that is bound by its copies. lam, the subtraction, the sub-norm and the
(1 - lam0) scale are computed as written, in float32 (`_diff_combine`).

STATE (kv_cache.HybridSlotCache): (a) pages for layer half+1, read by it
and every cross layer; (b) a ring of `window` rows per slot and window
layer, position t in row t % window — with no positional encoding the
order of the rows does not matter, so decode attends the ring's first
min(t + 1, window) rows with the contiguous flash_decode; (c) float32
conv-tail and SSM planes per Mamba layer.

ADMISSION (`admit_slot_paged`) runs layers 0..half over the whole
prompt, layer half+1's K/V projection over the whole prompt, and
everything after that on the LAST prompt position only: those layers
write no state, and the one logits row an admission returns depends on
nothing else. Mamba's scan is the chunked kernel; a prompt padded to its
bucket leaves the state of its last real token (`valid_len`).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from triton_dist_tpu.kernels import ssm
from triton_dist_tpu.kernels.quant import qmm
from triton_dist_tpu.layers import TP_MLP
from triton_dist_tpu.kernels.paged_kv import gather_pages, set_page_rows
from triton_dist_tpu.models.utils import ServingTraits, place_replicated
from triton_dist_tpu.runtime import auto_mesh

SUBLN_EPS = 1e-5
_PREFILL_Q = 256      # query rows per window-attention call of a prefill


@dataclasses.dataclass(frozen=True)
class Phi4FlashConfig:
    hidden_size: int = 2560
    intermediate_size: int = 10240
    num_layers: int = 32
    num_heads: int = 40
    num_kv_heads: int = 20
    vocab_size: int = 200064
    sliding_window: int = 512
    layer_norm_eps: float = 1e-5
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 160
    dtype: str = "bfloat16"
    model_type: str = "phi4flash"
    is_moe = False

    @property
    def jax_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @property
    def d_inner(self) -> int:
        return self.expand * self.hidden_size

    @property
    def half(self) -> int:
        return self.num_layers // 2

    def kind(self, li: int) -> str:
        if li % 2 == 0:
            return "mamba" if li <= self.half else "gmu"
        if li < self.half:
            return "swa"
        return "full" if li == self.half + 1 else "cross"

    def lambda_init(self, li: int) -> float:
        return 0.8 - 0.6 * math.exp(-0.3 * li)


def tiny_phi4flash(**overrides) -> Phi4FlashConfig:
    """Eight layers with all five kinds (mamba, swa, mamba, swa, mamba
    = half, full, gmu, cross), head size 64, a window shorter than a
    test's contexts: the tier-1 tests' model."""
    base = dict(hidden_size=256, intermediate_size=256, num_layers=8,
                num_heads=4, num_kv_heads=2, vocab_size=256,
                sliding_window=16, d_state=16, d_conv=4, expand=2,
                dt_rank=16, dtype="float32")
    base.update(overrides)
    return Phi4FlashConfig(**base)


# ----------------------------------------------------------------------
# weights
# ----------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass
class MambaMix:
    in_proj: jax.Array      # [D, 2E]
    conv_w: jax.Array       # [K, E] f32
    conv_b: jax.Array       # [E] f32
    x_proj: jax.Array       # [E, R + 2N]
    dt_w: jax.Array         # [R, E]
    dt_b: jax.Array         # [E] f32
    A_t: jax.Array          # [N, E] f32 = -exp(A_log)^T
    Dskip: jax.Array        # [E] f32
    out_proj: jax.Array     # [E, D]


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class AttnMix:
    wqkv: jax.Array         # [D, (Hq + 2 Hkv) hd]; a cross layer: [D, Hq hd]
    bqkv: jax.Array
    wo: jax.Array           # [Hq hd, D]
    bo: jax.Array
    lam: jax.Array          # [4, hd] f32: lq1, lk1, lq2, lk2
    subln: jax.Array        # [2 hd] f32


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class GmuMix:
    win: jax.Array          # [D, E]
    wout: jax.Array         # [E, D]


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class HybridLayer:
    mix: object             # MambaMix | AttnMix | GmuMix
    mlp: TP_MLP
    ln1_w: jax.Array
    ln1_b: jax.Array
    ln2_w: jax.Array
    ln2_b: jax.Array
    kind: str = dataclasses.field(metadata=dict(static=True))
    index: int = dataclasses.field(metadata=dict(static=True))


def layer_norm(x, w, b, eps: float):
    """LayerNorm in float32; the result keeps x's dtype."""
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
    out = (x32 - mu) * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32) \
        + b.astype(jnp.float32)
    return out.astype(x.dtype)


def _mm32(x, w):
    """Matmul in the weights' dtype with a float32 result (the state-
    space path keeps float32 between its small projections)."""
    return qmm(x.astype(w.dtype), w, preferred_element_type=jnp.float32)


def _attn_impl(mode: str) -> str:
    return "ref" if mode == "xla" else "flash"


# ----------------------------------------------------------------------
# differential attention on paired heads
# ----------------------------------------------------------------------

def _pad_queries(q):
    """q [..., Hq, hd] -> [..., Hq, 2 hd]: an even head becomes
    [q | 0] (it meets k1), an odd head [0 | q] (it meets k2)."""
    z = jnp.zeros_like(q)
    odd = (jnp.arange(q.shape[-2]) % 2 == 1)[:, None]
    return jnp.where(odd, jnp.concatenate([z, q], -1),
                     jnp.concatenate([q, z], -1))


def _diff_combine(o, mix: AttnMix, lam0: float):
    """o [..., Hq, 2 hd]: head 2j holds a1 of pair j, head 2j+1 its a2.
    Returns the pairs' normed, scaled difference [..., Hq * hd] f32."""
    lq1, lk1, lq2, lk2 = mix.lam.astype(jnp.float32)
    lam = jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2)) + lam0
    o = o.astype(jnp.float32)
    o = o.reshape(o.shape[:-2] + (o.shape[-2] // 2, 2, o.shape[-1]))
    a = o[..., 0, :] - lam * o[..., 1, :]
    a = a * jax.lax.rsqrt(jnp.mean(a * a, axis=-1, keepdims=True)
                          + SUBLN_EPS) * mix.subln.astype(jnp.float32)
    a = a * (1.0 - lam0)
    return a.reshape(a.shape[:-2] + (-1,))


def _one_query_attention(q, k, v, n, scale: float):
    """The last prompt position's padded queries q [Hq, d] over the
    prompt's pooled K/V [P, Hp, d], positions below n: plain XLA (one
    row of queries; nothing to tile). Returns [Hq, d] f32."""
    Hq, d = q.shape
    P_, Hp, _ = k.shape
    qg = q.reshape(Hp, Hq // Hp, d).astype(jnp.float32)
    s = jnp.einsum("hgd,thd->hgt", qg, k.astype(jnp.float32)) * scale
    s = jnp.where((jnp.arange(P_) < n)[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("hgt,thd->hgd", p,
                      v.astype(jnp.float32)).reshape(Hq, d)


def _window_prefill_attention(q, k, v, window: int, scale: float,
                              impl: str):
    """Causal sliding-window attention of a whole prompt: q [P, Hq, d]
    padded queries, k/v [P, Hp, d]. "flash": the cached-attention
    kernel, a block of query rows at a time over the span of keys that
    block can see. Returns [P, Hq, d]."""
    from triton_dist_tpu.kernels.flash_attn import (attention_cached_ref,
                                                    flash_decode)
    P_ = q.shape[0]
    kt = jnp.swapaxes(k, 0, 1)[None]            # [1, Hp, P, d]
    vt = jnp.swapaxes(v, 0, 1)[None]
    if impl == "ref":
        return attention_cached_ref(q[None], kt, vt, jnp.int32(P_),
                                    scale=scale, window=window)[0]
    outs = []
    for c0 in range(0, P_, _PREFILL_Q):
        c1 = min(P_, c0 + _PREFILL_Q)
        lo = max(0, c0 - window) // 8 * 8
        outs.append(flash_decode(
            q[None, c0:c1], kt[:, :, lo:c1], vt[:, :, lo:c1],
            jnp.int32(c1 - lo), scale=scale, window=window)[0])
    return jnp.concatenate(outs, axis=0)


# ----------------------------------------------------------------------
# the model
# ----------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass
class Phi4Flash:
    embed: jax.Array                    # [V, D]
    layers: Tuple[HybridLayer, ...]
    lnf_w: jax.Array
    lnf_b: jax.Array
    lm_head: jax.Array                  # [D, V]: the embedding transposed
    config: Phi4FlashConfig = dataclasses.field(metadata=dict(static=True))
    mesh: Mesh = dataclasses.field(metadata=dict(static=True))
    axis: str = dataclasses.field(metadata=dict(static=True))

    # -- construction --------------------------------------------------

    @staticmethod
    def make_layer(cfg: Phi4FlashConfig, li: int, w: dict, mesh: Mesh,
                   axis: str = "tp") -> HybridLayer:
        """One layer from a dict of plain arrays under the reference's
        names (benchmark/reference/phi4flash.py `_layer_weights`)."""
        kind = cfg.kind(li)
        f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
        if kind == "mamba":
            mix = MambaMix(
                in_proj=w["in_proj"], conv_w=f32(w["conv_w"]),
                conv_b=f32(w["conv_b"]), x_proj=w["x_proj"],
                dt_w=w["dt_w"], dt_b=f32(w["dt_b"]),
                A_t=-jnp.exp(f32(w["A_log"])).T, Dskip=f32(w["Dskip"]),
                out_proj=w["out_proj"])
        elif kind == "gmu":
            mix = GmuMix(win=w["win"], wout=w["wout"])
        else:
            mix = AttnMix(wqkv=w["wqkv"], bqkv=w["bqkv"], wo=w["wo"],
                          bo=w["bo"], lam=f32(w["lam"]),
                          subln=f32(w["subln"]))
        I = cfg.intermediate_size
        mlp = TP_MLP.init(w["w1"][:, :I], w["w1"][:, I:], w["w2"],
                          mesh=mesh, axis=axis)
        return HybridLayer(mix=mix, mlp=mlp, ln1_w=w["ln1_w"],
                           ln1_b=w["ln1_b"], ln2_w=w["ln2_w"],
                           ln2_b=w["ln2_b"], kind=kind, index=li)

    @staticmethod
    def build(cfg: Phi4FlashConfig, head: dict, layers, mesh: Mesh,
              axis: str = "tp") -> "Phi4Flash":
        """head: {"embed", "lnf_w", "lnf_b"}; layers: HybridLayers from
        `make_layer`. One chip: the mesh's `axis` must have size 1."""
        mesh = auto_mesh(mesh)
        if mesh.shape[axis] != 1:
            raise ValueError(
                f"Phi4Flash serves on one chip (mesh axis {axis!r} has "
                f"size {mesh.shape[axis]}); missing capability: tensor-"
                "parallel state-space and paired-head attention layers")
        if cfg.num_heads % 2 or cfg.num_kv_heads % 2 \
                or (cfg.num_heads // 2) % (cfg.num_kv_heads // 2):
            raise ValueError("differential attention pairs heads: "
                             "num_heads and num_kv_heads must be even "
                             "and their pairs must group")
        model = Phi4Flash(embed=head["embed"], layers=tuple(layers),
                          lnf_w=head["lnf_w"], lnf_b=head["lnf_b"],
                          lm_head=head["embed"].T, config=cfg, mesh=mesh,
                          axis=axis)
        return place_replicated(model, mesh)

    @staticmethod
    def random_init(cfg: Phi4FlashConfig, mesh: Mesh, axis: str = "tp",
                    seed: int = 0) -> "Phi4Flash":
        """Random weights for tests and examples (the benchmark brings
        its own, from its reference)."""
        mesh = auto_mesh(mesh)
        D, I, E = cfg.hidden_size, cfg.intermediate_size, cfg.d_inner
        N, K, R, hd = cfg.d_state, cfg.d_conv, cfg.dt_rank, cfg.head_dim
        nq, nkv = cfg.num_heads * hd, cfg.num_kv_heads * hd
        dt = cfg.jax_dtype
        kit = iter(jax.random.split(jax.random.key(seed), 4096))

        def w(*shape, scale=None, dtype=dt):
            s = scale if scale is not None else shape[0] ** -0.5
            return (jax.random.normal(next(kit), shape, jnp.float32)
                    * s).astype(dtype)

        one = lambda n, dtype=dt: (1.0 + w(n, scale=0.1,  # noqa: E731
                                           dtype=jnp.float32)).astype(dtype)
        layers = []
        for li in range(cfg.num_layers):
            kind = cfg.kind(li)
            d = {"ln1_w": one(D), "ln1_b": w(D, scale=0.02),
                 "ln2_w": one(D), "ln2_b": w(D, scale=0.02),
                 "w1": w(D, 2 * I), "w2": w(I, D)}
            if kind == "mamba":
                step = jnp.exp(jax.random.uniform(
                    next(kit), (E,), jnp.float32, math.log(1e-3),
                    math.log(1e-1)))
                d.update(
                    in_proj=w(D, 2 * E), x_proj=w(E, R + 2 * N),
                    conv_w=w(K, E, scale=0.5, dtype=jnp.float32),
                    conv_b=w(E, scale=0.02, dtype=jnp.float32),
                    dt_w=w(R, E), dt_b=step + jnp.log(-jnp.expm1(-step)),
                    A_log=jnp.broadcast_to(jnp.log(jnp.arange(
                        1, N + 1, dtype=jnp.float32)), (E, N)),
                    Dskip=one(E, jnp.float32), out_proj=w(E, D))
            elif kind == "gmu":
                d.update(win=w(D, E), wout=w(E, D))
            else:
                cols = nq if kind == "cross" else nq + 2 * nkv
                d.update(wqkv=w(D, cols), bqkv=w(cols, scale=0.02),
                         wo=w(nq, D), bo=w(D, scale=0.02),
                         lam=w(4, hd, scale=0.1, dtype=jnp.float32),
                         subln=one(2 * hd, jnp.float32))
            layers.append(Phi4Flash.make_layer(cfg, li, d, mesh, axis))
        head = {"embed": w(cfg.vocab_size, D, scale=0.02),
                "lnf_w": one(D), "lnf_b": w(D, scale=0.02)}
        return Phi4Flash.build(cfg, head, layers, mesh, axis)

    # -- what the Engine and the scheduler ask -------------------------

    def serving_traits(self) -> ServingTraits:
        return ServingTraits(kv_heads=self.config.num_kv_heads // 2,
                             slot_state="recurrent state")

    def make_paged_cache(self, batch: int, max_seq: int, *, page: int,
                         num_pages: int, dtype=None,
                         sp_axis: Optional[str] = None):
        from triton_dist_tpu.models.kv_cache import HybridSlotCache
        cfg = self.config
        kinds = [cfg.kind(li) for li in range(cfg.num_layers)]
        return HybridSlotCache.create_hybrid(
            batch, max_seq, heads=cfg.num_kv_heads // 2,
            head_dim=2 * cfg.head_dim, window=cfg.sliding_window,
            window_layers=kinds.count("swa"),
            state_layers=kinds.count("mamba"),
            conv_shape=(cfg.d_conv - 1, cfg.d_inner),
            state_shape=(cfg.d_state, cfg.d_inner),
            attn_layers=sum(k in ("swa", "full", "cross") for k in kinds),
            page=page,
            num_pages=num_pages, mesh=self.mesh, axis=self.axis,
            dtype=dtype or cfg.jax_dtype)

    # -- pieces both forwards share ------------------------------------

    def _qkv(self, layer: HybridLayer, u):
        """u [M, D] -> (padded queries [M, Hq, 2hd], pooled k, v
        [M, Hp, 2hd] or None for a cross layer)."""
        cfg = self.config
        hd, Hq, Hp = cfg.head_dim, cfg.num_heads, cfg.num_kv_heads // 2
        qkv = qmm(u, layer.mix.wqkv) + layer.mix.bqkv
        M = u.shape[0]
        nq, nkv = Hq * hd, 2 * Hp * hd
        q = _pad_queries(qkv[:, :nq].reshape(M, Hq, hd))
        if layer.kind == "cross":
            return q, None, None
        k = qkv[:, nq:nq + nkv].reshape(M, Hp, 2 * hd)
        v = qkv[:, nq + nkv:].reshape(M, Hp, 2 * hd)
        return q, k, v

    def _attn_out(self, layer: HybridLayer, o, dtype):
        """Paired attention outputs o [M, Hq, 2hd] -> the mix [M, D]."""
        a = _diff_combine(o, layer.mix,
                          self.config.lambda_init(layer.index))
        return qmm(a.astype(dtype), layer.mix.wo) + layer.mix.bo

    def _mamba_inputs(self, mix: MambaMix, xc):
        """The conv output xc [M, E] f32 -> (dt [M, E], B, C [M, N])."""
        R, N = self.config.dt_rank, self.config.d_state
        low = _mm32(xc, mix.x_proj)
        dt = jax.nn.softplus(_mm32(low[:, :R], mix.dt_w) + mix.dt_b)
        return dt, low[:, R:R + N], low[:, R + N:]

    def _mlp(self, layer: HybridLayer, h, mode: str):
        u = layer_norm(h, layer.ln2_w, layer.ln2_b,
                       self.config.layer_norm_eps)
        return h + layer.mlp(u, "xla" if mode == "xla" else "flash")

    def _logits(self, x):
        x = layer_norm(x, self.lnf_w, self.lnf_b,
                       self.config.layer_norm_eps)
        return qmm(x, self.lm_head, preferred_element_type=jnp.float32)

    # -- decode: one token for every slot ------------------------------

    def forward_tokens_slots_paged(self, ids, pcache, pos,
                                   mode: str = "flash",
                                   mlp_mode: Optional[str] = None):
        """Slot-masked decode over the hybrid cache: ids [B, 1], pos [B]
        (each slot's own position). Returns (logits [B, V], pcache)."""
        from triton_dist_tpu.kernels.flash_attn import (
            attention_cached_ref, flash_decode)
        from triton_dist_tpu.kernels.paged_kv import flash_decode_paged
        cfg = self.config
        B = ids.shape[0]
        Hp, d = cfg.num_kv_heads // 2, 2 * cfg.head_dim
        W, page = cfg.sliding_window, pcache.page
        scale = cfg.head_dim ** -0.5
        impl = _attn_impl(mode)
        _, step = ssm.by_mode(mode)
        pos = jnp.asarray(pos, jnp.int32)
        lens = pos + 1
        live = pcache.live
        table = pcache.table
        # layer half+1's pool: where this step's K/V row lands, and the
        # walk every reader of the pool makes
        X = B * Hp
        pos_x = jnp.repeat(pos, Hp)
        pidx = table[jnp.arange(B), pos // page]
        prow = pos % page
        pk, pv = pcache.pages_k[0], pcache.pages_v[0]

        def paged_attend(q):
            if impl == "flash":
                return flash_decode_paged(
                    q[:, None].astype(pk.dtype), pk, pv, table,
                    jnp.max(lens), scale=scale, kv_lens=lens)[:, 0]
            return attention_cached_ref(
                q[:, None].astype(pk.dtype), gather_pages(pk, table),
                gather_pages(pv, table), lens, scale=scale)[:, 0]

        x = self.embed[ids[:, 0]]
        win_k, win_v = list(pcache.win_k), list(pcache.win_v)
        conv, state = list(pcache.conv), list(pcache.ssm)
        i_win = i_ssm = 0
        memory = None
        for layer in self.layers:
            mix = layer.mix
            u = layer_norm(x, layer.ln1_w, layer.ln1_b, cfg.layer_norm_eps)
            with jax.named_scope(layer.kind):
                if layer.kind == "mamba":
                    xz = _mm32(u, mix.in_proj)
                    x_in, z = jnp.split(xz, 2, axis=-1)
                    taps = jnp.concatenate([conv[i_ssm], x_in[:, None]], 1)
                    xc = jax.nn.silu(mix.conv_b + jnp.sum(
                        mix.conv_w[None] * taps, axis=1))
                    dt, Bm, Cm = self._mamba_inputs(mix, xc)
                    y, state[i_ssm] = step(xc, dt, Bm, Cm, mix.A_t,
                                           mix.Dskip, state[i_ssm], live)
                    conv[i_ssm] = jnp.where(live[:, None, None],
                                            taps[:, 1:], conv[i_ssm])
                    i_ssm += 1
                    if layer.index == cfg.half:
                        memory = y
                    a = qmm((y * jax.nn.silu(z)).astype(x.dtype),
                            mix.out_proj)
                elif layer.kind == "gmu":
                    g = memory * jax.nn.silu(_mm32(u, mix.win))
                    a = qmm(g.astype(x.dtype), mix.wout)
                else:
                    q, k, v = self._qkv(layer, u)
                    if layer.kind == "swa":
                        # the ring as [streams, rows, d] for the write:
                        # two leading index dims scatter in place (a
                        # head axis between them costs three copies of
                        # the ring a layer a step)
                        ring = win_k[i_win].shape
                        rk = win_k[i_win].reshape(X, W, d).at[
                            jnp.arange(X), pos_x % W].set(
                            k.reshape(X, d).astype(win_k[i_win].dtype)
                        ).reshape(ring)
                        rv = win_v[i_win].reshape(X, W, d).at[
                            jnp.arange(X), pos_x % W].set(
                            v.reshape(X, d).astype(rk.dtype)).reshape(ring)
                        win_k[i_win], win_v[i_win] = rk, rv
                        i_win += 1
                        wl = jnp.minimum(lens, W)
                        qq = q[:, None].astype(rk.dtype)
                        o = (flash_decode(qq, rk, rv, jnp.max(wl),
                                          scale=scale, kv_lens=wl)
                             if impl == "flash" else
                             attention_cached_ref(qq, rk, rv, wl,
                                                  scale=scale))[:, 0]
                    else:
                        if layer.kind == "full":
                            pk = set_page_rows(pk, pidx, prow,
                                               k.reshape(B, Hp, d))
                            pv = set_page_rows(pv, pidx, prow,
                                               v.reshape(B, Hp, d))
                        o = paged_attend(q)
                    a = self._attn_out(layer, o, x.dtype)
            x = self._mlp(layer, x + a, mlp_mode or mode)
        pcache = dataclasses.replace(
            pcache, pages_k=(pk,), pages_v=(pv,),
            win_k=tuple(win_k), win_v=tuple(win_v), conv=tuple(conv),
            ssm=tuple(state))
        return self._logits(x), pcache

    # -- admission: a whole prompt into one slot -----------------------

    def admit_slot_paged(self, ids, pcache, rows, slot, n,
                         mode: str = "flash"):
        """ids [1, P]: the prompt, zero-padded to its bucket; n: its
        real length; rows [maxp]: the slot's table row. Installs
        the row, leaves the slot's pages, rings and planes as the
        prompt's last real token leaves them, and returns (logits
        [1, V] of that token, pcache)."""
        cfg = self.config
        P_ = ids.shape[1]
        Hp, d = cfg.num_kv_heads // 2, 2 * cfg.head_dim
        W, K, page = cfg.sliding_window, cfg.d_conv, pcache.page
        scale = cfg.head_dim ** -0.5
        impl = _attn_impl(mode)
        scan, _ = ssm.by_mode(mode)
        last = n - 1
        row = lambda a: jax.lax.dynamic_slice_in_dim(a, last, 1, 0)  # noqa
        p = jnp.arange(P_)
        # ring row r ends up holding the last prompt position congruent
        # to it (rows no position reached keep what they had: the
        # slot's lengths mask them until decode overwrites them)
        r = jnp.arange(W)
        src = r + (jnp.maximum(last - r, 0) // W) * W
        ring_ok = (r <= last)[None, :, None]
        src = jnp.minimum(src, P_ - 1)

        def to_ring(ring, new):                  # new [P, Hp, d]
            picked = jnp.swapaxes(new[src], 0, 1).astype(ring.dtype)
            cur = jax.lax.dynamic_slice_in_dim(ring, slot, 1, 0)[0]
            return jax.lax.dynamic_update_slice_in_dim(
                ring, jnp.where(ring_ok, picked, cur)[None], slot, 0)

        def put(plane, new):
            return jax.lax.dynamic_update_slice_in_dim(
                plane, new[None].astype(plane.dtype), slot, 0)

        x = self.embed[ids[0]]                   # [P, D], then [1, D]
        win_k, win_v = list(pcache.win_k), list(pcache.win_v)
        conv, state = list(pcache.conv), list(pcache.ssm)
        pk, pv = pcache.pages_k[0], pcache.pages_v[0]
        i_win = i_ssm = 0
        memory = kf = vf = None
        for layer in self.layers:
            mix = layer.mix
            u = layer_norm(x, layer.ln1_w, layer.ln1_b, cfg.layer_norm_eps)
            with jax.named_scope(layer.kind):
                if layer.kind == "mamba":
                    xz = _mm32(u, mix.in_proj)
                    x_in, z = jnp.split(xz, 2, axis=-1)
                    xp = jnp.pad(x_in, ((K - 1, 0), (0, 0)))
                    xc = jax.nn.silu(mix.conv_b + sum(
                        mix.conv_w[k] * xp[k:k + P_] for k in range(K)))
                    dt, Bm, Cm = self._mamba_inputs(mix, xc)
                    y, s_last = scan(
                        xc, dt, Bm, Cm, mix.A_t, mix.Dskip,
                        jnp.zeros_like(state[i_ssm][0]), n)
                    state[i_ssm] = put(state[i_ssm], s_last)
                    # positions n-K+1 .. n-1 sit at xp[n .. n+K-2]
                    conv[i_ssm] = put(conv[i_ssm],
                                      jax.lax.dynamic_slice_in_dim(
                                          xp, n, K - 1, 0))
                    i_ssm += 1
                    if layer.index == cfg.half:
                        memory = row(y)
                    a = qmm((y * jax.nn.silu(z)).astype(x.dtype),
                            mix.out_proj)
                elif layer.kind == "gmu":
                    g = memory * jax.nn.silu(_mm32(u, mix.win))
                    a = qmm(g.astype(x.dtype), mix.wout)
                elif layer.kind == "swa":
                    q, k, v = self._qkv(layer, u)
                    win_k[i_win] = to_ring(win_k[i_win], k)
                    win_v[i_win] = to_ring(win_v[i_win], v)
                    i_win += 1
                    kd = win_k[0].dtype
                    o = _window_prefill_attention(
                        q.astype(kd), k.astype(kd), v.astype(kd), W, scale,
                        impl)
                    a = self._attn_out(layer, o, x.dtype)
                else:
                    if layer.kind == "full":
                        # K/V of the whole prompt into the slot's pages;
                        # from here on only the last position goes on
                        q, kf, vf = self._qkv(layer, u)
                        kf, vf = kf.astype(pk.dtype), vf.astype(pv.dtype)
                        dest = jnp.where(
                            p < n,
                            rows[jnp.minimum(p // page,
                                             rows.shape[0] - 1)],
                            pcache.trash)                    # [P]
                        pk = set_page_rows(pk, dest, p % page, kf)
                        pv = set_page_rows(pv, dest, p % page, vf)
                        x, q = row(x), row(q)
                    else:
                        q, _, _ = self._qkv(layer, u)
                    o = _one_query_attention(
                        q[0].astype(kf.dtype), kf, vf, n, scale)[None]
                    a = self._attn_out(layer, o, x.dtype)
            x = self._mlp(layer, x + a, mode)
        table = jax.lax.dynamic_update_slice(
            pcache.table, rows[None], (slot, 0))
        pcache = dataclasses.replace(
            pcache, pages_k=(pk,), pages_v=(pv,),
            table=table, win_k=tuple(win_k), win_v=tuple(win_v),
            conv=tuple(conv), ssm=tuple(state),
            live=pcache.live.at[slot].set(True))
        return self._logits(x), pcache
