"""Falcon-H1 (`model_type: falcon_h1`, tiiuae/Falcon-H1-34B-Instruct) on
one chip, served through the same Engine / scheduler / TokenServer path
as the other families.

    x = E[ids] * embedding_multiplier
    h = RMSNorm_in(x)
    x = x + Mamba2(h) * ssm_out + Attn(h * attention_in) * attention_out
    x = x + MLP(RMSNorm_ff(x))
    logits = (W_head RMSNorm_f(x)) * lm_head_multiplier

Every layer has BOTH mixers, reading the same normed input in parallel
(`attn_layer_indices` null):

  Mamba2  layers/mamba2.py: the SSD recurrence of kernels/ssd.py over
          `mamba_n_heads` heads of `mamba_d_head` channels, a scalar
          decay a head, B and C shared by the heads of each of
          `mamba_n_groups` groups, a [`mamba_d_state`, head_dim] float32
          state a head; the grouped gated RMSNorm.
  Attn    plain GQA with rotary on all head_dim dims, no biases, no
          QK-norm, keys times `key_multiplier`: `layers/tp_attn.py`
          TP_Attn on a one-chip mesh, its paged decode walk named
          `fh_attn` in a trace.
  MLP     SwiGLU: W_down(W_up u * silu((W_gate u) * m_gate)) * m_down
          (`mlp_multipliers`): `layers/tp_mlp.py` TP_MLP.

Seven of the nine published multipliers are FOLDED into the weights at
construction (`make_layer`), which is exact in real arithmetic:
`attention_in` into W_q, W_k, W_v, `key_multiplier` into W_k,
`attention_out` into W_o, the two MLP multipliers into W_gate and
W_down, the SSM's into W_in and W_out. The embedding's and the head's
are applied to the rows a step gathers and to its logits, where they
cost nothing and leave the two tables (a quarter of the weights) as
they were made. benchmark/reference/falcon_h1.py applies them all as
published.

STATE (kv_cache.HybridSlotCache): every layer has its pages (K and V
in two planes behind the shared table) AND a conv plane and an SSD
state plane per slot. A slot therefore holds state beside its pages
(`ServingTraits.slot_state` "ssm state"): it admits through
`admit_slot_paged` below, and whatever would rebuild a slot from pages
alone is refused by that name.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from triton_dist_tpu.kernels.paged_kv import set_prompt_pages
from triton_dist_tpu.kernels.quant import qmm
from triton_dist_tpu.layers.common import apply_rope, precompute_rope, \
    rms_norm
from triton_dist_tpu.layers.gated_attn import prefill_attention
from triton_dist_tpu.layers.mamba2 import Mamba2, fold
from triton_dist_tpu.layers.tp_attn import TP_Attn
from triton_dist_tpu.layers.tp_mlp import TP_MLP
from triton_dist_tpu.models.utils import ServingTraits, place_replicated
from triton_dist_tpu.runtime import auto_mesh


@dataclasses.dataclass(frozen=True)
class FalconH1Config:
    hidden_size: int = 5120
    intermediate_size: int = 21504
    num_layers: int = 72
    num_heads: int = 20
    num_kv_heads: int = 4
    head_dim: int = 128
    mamba_n_heads: int = 32
    mamba_d_head: int = 128
    mamba_d_state: int = 256
    mamba_n_groups: int = 2
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 128
    vocab_size: int = 261120
    max_position_embeddings: int = 262144
    rope_theta: float = 1e11
    rms_norm_eps: float = 1e-5
    embedding_multiplier: float = 5.656854249492381
    lm_head_multiplier: float = 0.0078125
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 0.0375
    key_multiplier: float = 0.011048543456039804
    ssm_in_multiplier: float = 0.25
    ssm_out_multiplier: float = 0.08838834764831845
    # z, x, B, C, dt segments of the SSM's input projection
    ssm_multipliers: Tuple[float, ...] = (
        0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
        0.3535533905932738)
    mlp_multipliers: Tuple[float, float] = (0.1767766952966369,
                                            0.011160714285714284)
    dtype: str = "bfloat16"
    model_type: str = "falcon_h1"
    is_moe = False

    @property
    def jax_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def d_ssm(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_channels(self) -> int:
        return self.d_ssm + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def state_layers(self) -> int:
        """Layers whose SSD state a live slot advances every decode
        step (the scheduler's `ssm_state_updates`): all of them."""
        return self.num_layers


def tiny_falcon_h1(**overrides) -> FalconH1Config:
    """Two layers of D 64; 4 query heads on 2 KV heads of 16; 4 SSM
    heads of 16 channels in 2 groups, a state of 16, chunks of 8: the
    tier-1 tests' model."""
    base = dict(hidden_size=64, intermediate_size=128, num_layers=2,
                num_heads=4, num_kv_heads=2, head_dim=16, mamba_n_heads=4,
                mamba_d_head=16, mamba_d_state=16, mamba_n_groups=2,
                mamba_chunk_size=8, vocab_size=256,
                max_position_embeddings=256, rope_theta=10000.0,
                dtype="float32")
    base.update(overrides)
    return FalconH1Config(**base)


def _one_chip(mesh: Mesh, axis: str) -> Mesh:
    mesh = auto_mesh(mesh)
    if mesh.shape[axis] != 1:
        raise ValueError(
            f"FalconH1 serves one chip (mesh axis {axis!r} has size "
            f"{mesh.shape[axis]}); missing capability: tensor-parallel "
            "SSM heads")
    return mesh


def _scaled(w, m: float):
    return fold(jnp.asarray(w), jnp.float32(m))


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class FalconH1Layer:
    attn: TP_Attn
    mamba: Mamba2
    mlp: TP_MLP
    ln_in: jax.Array
    ln_ff: jax.Array


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class FalconH1:
    embed: jax.Array                   # [V, D]
    layers: Tuple[FalconH1Layer, ...]
    final_norm: jax.Array
    lm_head: jax.Array                 # [D, V], untied
    cos: jax.Array                     # [T, head_dim / 2]
    sin: jax.Array
    config: FalconH1Config = dataclasses.field(metadata=dict(static=True))
    mesh: Mesh = dataclasses.field(metadata=dict(static=True))
    axis: str = dataclasses.field(metadata=dict(static=True))

    # -- construction --------------------------------------------------

    @staticmethod
    def make_layer(cfg: FalconH1Config, w: dict, mesh: Mesh,
                   axis: str = "tp") -> FalconH1Layer:
        """One layer from a dict of plain arrays under the reference's
        names (benchmark/reference/falcon_h1.py `_layer_weights`), the
        published tensors with no multiplier applied."""
        mesh = _one_chip(mesh, axis)
        a_in = cfg.attention_in_multiplier
        attn = TP_Attn.init(
            _scaled(w["wq"], a_in), _scaled(w["wk"], a_in
                                             * cfg.key_multiplier),
            _scaled(w["wv"], a_in),
            _scaled(w["wo"], cfg.attention_out_multiplier), mesh=mesh,
            axis=axis, n_heads=cfg.num_heads, n_kv_heads=cfg.num_kv_heads,
            head_dim=cfg.head_dim, scope="fh_attn")
        mamba = Mamba2.init(
            w["w_in"], w["conv_w"], w["conv_b"], w["dt_bias"], w["A_log"],
            w["D"], w["ssm_norm"], w["w_out"], n_heads=cfg.mamba_n_heads,
            head_dim=cfg.mamba_d_head, n_groups=cfg.mamba_n_groups,
            d_state=cfg.mamba_d_state, chunk=cfg.mamba_chunk_size,
            eps=cfg.rms_norm_eps, in_mult=cfg.ssm_in_multiplier,
            mup=cfg.ssm_multipliers, out_mult=cfg.ssm_out_multiplier)
        m_gate, m_down = cfg.mlp_multipliers
        mlp = TP_MLP.init(_scaled(w["w_gate"], m_gate), w["w_up"],
                          _scaled(w["w_down"], m_down), mesh=mesh,
                          axis=axis)
        return FalconH1Layer(attn=attn, mamba=mamba, mlp=mlp,
                             ln_in=w["ln_in"], ln_ff=w["ln_ff"])

    @staticmethod
    def build(cfg: FalconH1Config, head: dict, layers, mesh: Mesh,
              axis: str = "tp") -> "FalconH1":
        """head: {"embed", "final_norm", "lm_head"} as published; layers
        from `make_layer`. One chip: the mesh's `axis` must have size
        1."""
        mesh = _one_chip(mesh, axis)
        cos, sin = precompute_rope(cfg.head_dim,
                                   cfg.max_position_embeddings,
                                   cfg.rope_theta)
        model = FalconH1(
            embed=head["embed"], layers=tuple(layers),
            final_norm=head["final_norm"], lm_head=head["lm_head"],
            cos=cos, sin=sin, config=cfg, mesh=mesh, axis=axis)
        return place_replicated(model, mesh)

    @staticmethod
    def random_init(cfg: FalconH1Config, mesh: Mesh, axis: str = "tp",
                    seed: int = 0) -> "FalconH1":
        """Random weights for tests and examples (the benchmark brings
        its own, from its reference)."""
        mesh = auto_mesh(mesh)
        D, I = cfg.hidden_size, cfg.intermediate_size
        nq, nkv = cfg.num_heads * cfg.head_dim, \
            cfg.num_kv_heads * cfg.head_dim
        H, Ei, C = cfg.mamba_n_heads, cfg.d_ssm, cfg.conv_channels
        dt = cfg.jax_dtype
        kit = iter(jax.random.split(jax.random.key(seed), 1024))

        def w(*shape, scale=None, dtype=dt):
            s = scale if scale is not None else shape[-2] ** -0.5
            return (jax.random.normal(next(kit), shape, jnp.float32)
                    * s).astype(dtype)

        one = lambda n: (1.0 + w(n, scale=0.1,  # noqa: E731
                                 dtype=jnp.float32)).astype(dt)
        f32 = jnp.float32
        layers = []
        for _ in range(cfg.num_layers):
            d = {"ln_in": one(D), "ln_ff": one(D),
                 "wq": w(D, nq), "wk": w(D, nkv), "wv": w(D, nkv),
                 "wo": w(nq, D), "w_in": w(D, Ei + C + H),
                 "conv_w": w(cfg.mamba_d_conv, C, scale=0.5, dtype=f32),
                 "conv_b": w(C, scale=0.02, dtype=f32),
                 "dt_bias": jnp.full((H,), -2.0, f32),
                 "A_log": jnp.log(jnp.arange(1, H + 1, dtype=f32)),
                 "D": jnp.ones((H,), f32), "ssm_norm": one(Ei),
                 "w_out": w(Ei, D), "w_gate": w(D, I), "w_up": w(D, I),
                 "w_down": w(I, D)}
            layers.append(FalconH1.make_layer(cfg, d, mesh, axis))
        head = {"embed": w(cfg.vocab_size, D, scale=0.25),
                "final_norm": one(D),
                "lm_head": w(D, cfg.vocab_size, scale=D ** -0.5)}
        return FalconH1.build(cfg, head, layers, mesh, axis)

    # -- what the Engine and the scheduler ask -------------------------

    def serving_traits(self) -> ServingTraits:
        return ServingTraits(kv_heads=self.config.num_kv_heads,
                             slot_state="ssm state")

    def make_paged_cache(self, batch: int, max_seq: int, *, page: int,
                         num_pages: int, dtype=None,
                         sp_axis: Optional[str] = None):
        from triton_dist_tpu.models.kv_cache import HybridSlotCache
        cfg = self.config
        L = cfg.num_layers
        return HybridSlotCache.create_hybrid(
            batch, max_seq, heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
            window=0, window_layers=0, state_layers=L, attn_layers=L,
            page=page, num_pages=num_pages, mesh=self.mesh, axis=self.axis,
            dtype=dtype or cfg.jax_dtype, paged_layers=L,
            conv_shape=(cfg.mamba_d_conv - 1, cfg.conv_channels),
            state_shape=(cfg.mamba_n_heads, cfg.mamba_d_state,
                         cfg.mamba_d_head))

    # -- pieces both forwards share ------------------------------------

    def _embed(self, ids):
        x = self.embed[ids]
        return (x.astype(jnp.float32)
                * self.config.embedding_multiplier).astype(x.dtype)

    def _mlp(self, layer: FalconH1Layer, x, mode: str):
        u = rms_norm(x, layer.ln_ff, self.config.rms_norm_eps)
        return x + layer.mlp(u, "xla" if mode == "xla" else "flash")

    def _logits(self, x):
        x = rms_norm(x, self.final_norm, self.config.rms_norm_eps)
        return qmm(x, self.lm_head, preferred_element_type=jnp.float32) \
            * self.config.lm_head_multiplier

    def _attn_prefill(self, attn: TP_Attn, h, *, impl: str, dtype):
        """A prompt's attention on one chip: h [P, D] at positions
        0 .. P-1. Returns (y [P, D], k, v [P, Hkv, d] in `dtype`, the
        keys rotated, as the pages hold them)."""
        P_ = h.shape[0]
        Hq, Hkv, d = attn.n_heads, attn.n_kv_heads, attn.head_dim
        qkv = qmm(h, attn.w_qkv)               # [q | k | v] on one chip
        q = qkv[:, :Hq * d].reshape(P_, Hq, d)
        k = qkv[:, Hq * d:(Hq + Hkv) * d].reshape(P_, Hkv, d)
        v = qkv[:, (Hq + Hkv) * d:].reshape(P_, Hkv, d)
        at = jnp.arange(P_)
        q = apply_rope(q, self.cos, self.sin, at)
        k = apply_rope(k, self.cos, self.sin, at).astype(dtype)
        v = v.astype(dtype)
        o = prefill_attention(q.astype(dtype), k, v, window=0,
                              scale=d ** -0.5, impl=impl,
                              scope="fh_attn_prefill")
        return qmm(o.reshape(P_, Hq * d).astype(h.dtype), attn.w_o), k, v

    # -- decode: one token for every slot ------------------------------

    def forward_tokens_slots_paged(self, ids, pcache, pos,
                                   mode: str = "flash"):
        """Slot-masked decode over the hybrid cache: ids [B, 1], pos [B]
        (each slot's own position). Every layer appends its K/V row and
        advances the live slots' SSD and conv planes. Returns (logits
        [B, V], pcache)."""
        cfg = self.config
        B = ids.shape[0]
        pos = jnp.asarray(pos, jnp.int32)
        live = pcache.live
        x = self._embed(ids[:, 0])
        pk, pv = list(pcache.pages_k), list(pcache.pages_v)
        conv, state = list(pcache.conv), list(pcache.ssm)
        for i, layer in enumerate(self.layers):
            h = rms_norm(x, layer.ln_in, cfg.rms_norm_eps)
            m, conv[i], state[i] = layer.mamba.decode(
                h, conv[i], state[i], live, mode)
            a, (pk[i], pv[i]) = layer.attn.fwd_cached_slots_paged(
                h, self.cos, self.sin, B, (pk[i], pv[i]), pcache.table,
                pos, mode)
            x = self._mlp(layer, x + m + a, mode)
        pcache = dataclasses.replace(
            pcache, pages_k=tuple(pk), pages_v=tuple(pv), conv=tuple(conv),
            ssm=tuple(state))
        return self._logits(x), pcache

    # -- admission: a whole prompt into one slot -----------------------

    def admit_slot_paged(self, ids, pcache, rows, slot, n,
                         mode: str = "flash"):
        """ids [1, P]: the prompt, zero-padded to its bucket; n: its
        real length; rows [maxp]: the slot's table row. Installs the
        row, leaves the slot's pages holding the prompt's K and V and
        its planes the conv inputs and SSD state of its last real
        token, and returns (logits [1, V] of that token, pcache)."""
        cfg = self.config
        impl = "ref" if mode == "xla" else "flash"
        P_ = ids.shape[1]
        page = pcache.page
        kd = pcache.pages_k[0].dtype
        row = lambda a: jax.lax.dynamic_slice_in_dim(a, n - 1, 1, 0)  # noqa

        def put(plane, new):
            return jax.lax.dynamic_update_slice_in_dim(
                plane, new[None].astype(plane.dtype), slot, 0)

        # the prompt's pages; the trash page for one wholly past its end
        npg = -(-P_ // page)
        pids = jnp.where(jnp.arange(npg) * page < n, rows[:npg],
                         pcache.trash)
        x = self._embed(ids[0])                  # [P, D], then [1, D]
        pk, pv = list(pcache.pages_k), list(pcache.pages_v)
        conv, state = list(pcache.conv), list(pcache.ssm)
        for i, layer in enumerate(self.layers):
            h = rms_norm(x, layer.ln_in, cfg.rms_norm_eps)
            m, tail, s_last = layer.mamba.prefill(h, n, mode)
            conv[i], state[i] = put(conv[i], tail), put(state[i], s_last)
            a, k, v = self._attn_prefill(layer.attn, h, impl=impl,
                                         dtype=kd)
            pk[i] = set_prompt_pages(pk[i], pids, k)
            pv[i] = set_prompt_pages(pv[i], pids, v)
            x = x + m + a
            if i == len(self.layers) - 1:
                x = row(x)            # only the last position goes on
            x = self._mlp(layer, x, mode)
        table = jax.lax.dynamic_update_slice(pcache.table, rows[None],
                                             (slot, 0))
        pcache = dataclasses.replace(
            pcache, pages_k=tuple(pk), pages_v=tuple(pv), conv=tuple(conv),
            ssm=tuple(state), table=table,
            live=pcache.live.at[slot].set(True))
        return self._logits(x), pcache
