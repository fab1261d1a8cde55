"""DeepSeek-V3 (`model_type: deepseek_v3`) as ONE CHIP'S SHARE of an
expert-parallel deployment, served through the same Engine / scheduler
/ TokenServer path as the other families.

    x = x + MLA(RMSNorm(x));  x = x + FFN(RMSNorm(x))

FFN is the dense SwiGLU (`TP_MLP`) in the first `first_k_dense_replace`
layers and Shared(u) + Routed(u) after them: the shared expert a
`TP_MLP` computed whole, the routed part an `EP_MoE` with a STATED SHARE
(`held = (first, count)` of `n_routed_experts`): grouped sigmoid routing
over every published expert (`noaux_tc`), the chosen experts this chip
holds computed through the ragged grouped GEMM (layers/ep_moe.py
`fwd_share`: no dispatch, no combine, no capacity, nothing dropped), and
what the other chips of the layer would add LEFT OUT. The equations are
written out in benchmark/reference/deepseek_v3.py, which the tier-1
tests hold this module to, share for share.

ATTENTION is latent (layers/mla_attn.py): a prompt is admitted in
expanded form, decode runs absorbed over the LATENT POOL
(kv_cache.LatentSlotCache: one plane a layer, [c_kv | k_pe] a position,
no V plane). A slot's whole context is its pages (`slot_state` None),
but the Engine's own page-moving programs (admission through a
contiguous scratch, copy-on-write, host-tier gather and restore) move K
and V planes; a latent pool says so (`ServingTraits.own_pool`), admits
through `admit_slot_paged` below, and everything that would rebuild a
slot through those programs is refused by name until it is written
(ROADMAP Queue 2).

Multi-token prediction (`num_nextn_predict_layers`) is not built.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from triton_dist_tpu.kernels.quant import qmm
from triton_dist_tpu.layers.common import rms_norm
from triton_dist_tpu.layers.ep_moe import EP_MoE
from triton_dist_tpu.layers.mla_attn import (MLA_Attn, yarn_mscale,
                                             yarn_tables)
from triton_dist_tpu.layers.tp_mlp import TP_MLP
from triton_dist_tpu.models.utils import ServingTraits, place_replicated
from triton_dist_tpu.runtime import auto_mesh


@dataclasses.dataclass(frozen=True)
class DeepSeekConfig:
    hidden_size: int = 7168
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_layers: int = 61
    first_k_dense_replace: int = 3
    num_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 256      # what the router ranks
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    routed_scaling_factor: float = 2.5
    # the share: experts held_first .. held_first + held_count - 1
    held_first: int = 0
    held_count: int = 256
    vocab_size: int = 129280
    max_position_embeddings: int = 163840
    rope_theta: float = 10000.0
    rope_factor: float = 40.0
    rope_original_max: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    rms_norm_eps: float = 1e-6
    dtype: str = "bfloat16"
    model_type: str = "deepseek_v3"
    is_moe = True

    @property
    def jax_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def num_experts(self) -> int:
        """The experts whose load this chip reports
        (`expert_tokens{expert=}`): the held ones."""
        return self.held_count

    @property
    def expert_ids(self) -> range:
        return range(self.held_first, self.held_first + self.held_count)

    @property
    def softmax_scale(self) -> float:
        qk = self.qk_nope_head_dim + self.qk_rope_head_dim
        return qk ** -0.5 * yarn_mscale(self.rope_factor,
                                        self.rope_mscale_all_dim) ** 2

    def kind(self, li: int) -> str:
        return "dense" if li < self.first_k_dense_replace else "moe"


def tiny_deepseek(**overrides) -> DeepSeekConfig:
    """Three layers (dense, moe, moe), 4 heads, 16 routed experts in 4
    groups of which share 1 of 4 holds four: the tier-1 tests' model."""
    base = dict(hidden_size=128, intermediate_size=256,
                moe_intermediate_size=128, num_layers=3,
                first_k_dense_replace=1, num_heads=4, q_lora_rank=64,
                kv_lora_rank=128, qk_nope_head_dim=32, qk_rope_head_dim=16,
                v_head_dim=32, n_routed_experts=16, num_experts_per_tok=4,
                n_group=4, topk_group=2, held_first=4, held_count=4,
                vocab_size=256, max_position_embeddings=512,
                rope_original_max=64, dtype="float32")
    base.update(overrides)
    return DeepSeekConfig(**base)


def _one_chip(mesh: Mesh, axis: str) -> Mesh:
    mesh = auto_mesh(mesh)
    if mesh.shape[axis] != 1:
        raise ValueError(
            f"DeepSeekV3 serves one chip's share (mesh axis {axis!r} has "
            f"size {mesh.shape[axis]}); missing capability: "
            "tensor-parallel latent attention (a latent pool has one "
            "head to split) and the expert exchange across a mesh")
    return mesh


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class DeepSeekLayer:
    attn: MLA_Attn
    ln_attn: jax.Array
    ln_mlp: jax.Array
    mlp: TP_MLP                        # the dense FFN, or the shared expert
    moe: Optional[EP_MoE]              # None in a dense layer
    kind: str = dataclasses.field(metadata=dict(static=True))


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class DeepSeekV3:
    embed: jax.Array                   # [V, D]
    layers: Tuple[DeepSeekLayer, ...]
    final_norm: jax.Array
    lm_head: jax.Array                 # [D, V], untied
    cos: jax.Array
    sin: jax.Array
    config: DeepSeekConfig = dataclasses.field(metadata=dict(static=True))
    mesh: Mesh = dataclasses.field(metadata=dict(static=True))
    axis: str = dataclasses.field(metadata=dict(static=True))

    # -- construction --------------------------------------------------

    @staticmethod
    def make_layer(cfg: DeepSeekConfig, li: int, w: dict, mesh: Mesh,
                   axis: str = "tp") -> DeepSeekLayer:
        """One layer from a dict of plain arrays under the reference's
        names (benchmark/reference/deepseek_v3.py `_layer_weights`); an
        expert layer's `we_*` hold the HELD experts only."""
        mesh = _one_chip(mesh, axis)
        kind = cfg.kind(li)
        attn = MLA_Attn.init(
            w["w_qa"], w["q_norm"], w["w_qb"], w["w_kva"], w["kv_norm"],
            w["w_kvb"], w["w_o"], n_heads=cfg.num_heads,
            nope=cfg.qk_nope_head_dim, rope=cfg.qk_rope_head_dim,
            vd=cfg.v_head_dim, scale=cfg.softmax_scale,
            eps=cfg.rms_norm_eps)
        moe = None
        if kind == "dense":
            mlp = TP_MLP.init(w["w_gate"], w["w_up"], w["w_down"],
                              mesh=mesh, axis=axis)
        else:
            mlp = TP_MLP.init(w["ws_gate"], w["ws_up"], w["ws_down"],
                              mesh=mesh, axis=axis)
            moe = EP_MoE.init(
                w["w_router"], w["we_gate"], w["we_up"], w["we_down"],
                mesh=mesh, axis=axis, top_k=cfg.num_experts_per_tok,
                capacity_factor="dropless",
                held=(cfg.held_first, cfg.held_count), e_bias=w["e_bias"],
                noaux=(cfg.n_group, cfg.topk_group,
                       cfg.routed_scaling_factor))
        return DeepSeekLayer(attn=attn, ln_attn=w["ln_attn"],
                             ln_mlp=w["ln_mlp"], mlp=mlp, moe=moe,
                             kind=kind)

    @staticmethod
    def build(cfg: DeepSeekConfig, head: dict, layers, mesh: Mesh,
              axis: str = "tp") -> "DeepSeekV3":
        """head: {"embed", "final_norm", "lm_head"}; layers from
        `make_layer`. One chip: the mesh's `axis` must have size 1."""
        mesh = _one_chip(mesh, axis)
        if cfg.held_first < 0 or cfg.held_first + cfg.held_count \
                > cfg.n_routed_experts:
            raise ValueError(
                f"the share ({cfg.held_first}, {cfg.held_count}) lies "
                f"outside the {cfg.n_routed_experts} routed experts")
        cos, sin = yarn_tables(
            cfg.qk_rope_head_dim, cfg.max_position_embeddings,
            cfg.rope_theta, factor=cfg.rope_factor,
            original_max=cfg.rope_original_max,
            beta_fast=cfg.rope_beta_fast, beta_slow=cfg.rope_beta_slow,
            mscale=cfg.rope_mscale,
            mscale_all_dim=cfg.rope_mscale_all_dim)
        model = DeepSeekV3(
            embed=head["embed"], layers=tuple(layers),
            final_norm=head["final_norm"], lm_head=head["lm_head"],
            cos=cos, sin=sin, config=cfg, mesh=mesh, axis=axis)
        return place_replicated(model, mesh)

    @staticmethod
    def random_init(cfg: DeepSeekConfig, mesh: Mesh, axis: str = "tp",
                    seed: int = 0) -> "DeepSeekV3":
        """Random weights for tests and examples (the benchmark brings
        its own, from its reference)."""
        mesh = auto_mesh(mesh)
        D, I, F = (cfg.hidden_size, cfg.intermediate_size,
                   cfg.moe_intermediate_size)
        H, Rq, Rkv = cfg.num_heads, cfg.q_lora_rank, cfg.kv_lora_rank
        nope, rope, vd = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                          cfg.v_head_dim)
        dt = cfg.jax_dtype
        kit = iter(jax.random.split(jax.random.key(seed), 1024))

        def w(*shape, scale=None, dtype=dt):
            s = scale if scale is not None else shape[-2] ** -0.5
            return (jax.random.normal(next(kit), shape, jnp.float32)
                    * s).astype(dtype)

        one = lambda n: (1.0 + w(n, scale=0.1,  # noqa: E731
                                 dtype=jnp.float32)).astype(dt)
        layers = []
        for li in range(cfg.num_layers):
            d = {"ln_attn": one(D), "ln_mlp": one(D),
                 "w_qa": w(D, Rq), "q_norm": one(Rq),
                 "w_qb": w(Rq, H * (nope + rope)),
                 "w_kva": w(D, Rkv + rope), "kv_norm": one(Rkv),
                 "w_kvb": w(Rkv, H * (nope + vd)), "w_o": w(H * vd, D)}
            if cfg.kind(li) == "dense":
                d.update(w_gate=w(D, I), w_up=w(D, I), w_down=w(I, D))
            else:
                E, Eh = cfg.n_routed_experts, cfg.held_count
                Fs = cfg.n_shared_experts * F
                d.update(w_router=w(D, E),
                         e_bias=w(E, scale=0.02, dtype=jnp.float32),
                         ws_gate=w(D, Fs), ws_up=w(D, Fs),
                         ws_down=w(Fs, D), we_gate=w(Eh, D, F),
                         we_up=w(Eh, D, F), we_down=w(Eh, F, D))
            layers.append(DeepSeekV3.make_layer(cfg, li, d, mesh, axis))
        head = {"embed": w(cfg.vocab_size, D, scale=0.02),
                "final_norm": one(D),
                "lm_head": w(D, cfg.vocab_size, scale=0.02)}
        return DeepSeekV3.build(cfg, head, layers, mesh, axis)

    # -- what the Engine and the scheduler ask -------------------------

    def serving_traits(self) -> ServingTraits:
        return ServingTraits(kv_heads=1, own_pool="a latent pool")

    def make_paged_cache(self, batch: int, max_seq: int, *, page: int,
                         num_pages: int, dtype=None,
                         sp_axis: Optional[str] = None):
        from triton_dist_tpu.models.kv_cache import LatentSlotCache
        cfg = self.config
        return LatentSlotCache.create_latent(
            cfg.num_layers, batch, max_seq, rank=cfg.kv_lora_rank,
            rope=cfg.qk_rope_head_dim,
            expanded_row_values=cfg.num_heads * (
                cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
                + cfg.v_head_dim),
            page=page, num_pages=num_pages, mesh=self.mesh,
            dtype=dtype or cfg.jax_dtype)

    def _zero_load(self):
        """Fresh routing-load accumulator of a tick: [expert_tokens of
        the held experts, capacity_dropped (0: there is no capacity),
        pairs routed over every expert, pairs that landed on held
        ones]."""
        return jnp.zeros((self.config.held_count + 3,), jnp.int32)

    # -- pieces both forwards share ------------------------------------

    def _ffn(self, layer: DeepSeekLayer, x, mode: str, load):
        """x + FFN(RMSNorm(x)); `load` accumulates the expert layer's
        routing stats when the caller asked for them."""
        u = rms_norm(x, layer.ln_mlp, self.config.rms_norm_eps)
        mlp_mode = "xla" if mode == "xla" else "flash"
        if layer.kind == "dense":
            with jax.named_scope("dense_mlp"):
                return x + layer.mlp(u, mlp_mode), load
        with jax.named_scope("moe_shared"):
            y = layer.mlp(u, mlp_mode)
        r, st = layer.moe.fwd_share(u, return_stats=True)
        if load is not None:
            load = load + jnp.concatenate([
                st["expert_tokens"], st["dropped"].reshape(1),
                st["pairs_routed"].reshape(1),
                st["pairs_held"].reshape(1)]).astype(jnp.int32)
        return x + y + r.astype(x.dtype), load

    def _logits(self, x):
        x = rms_norm(x, self.final_norm, self.config.rms_norm_eps)
        return qmm(x, self.lm_head, preferred_element_type=jnp.float32)

    # -- decode: one token for every slot ------------------------------

    def forward_tokens_slots_paged(self, ids, pcache, pos,
                                   mode: str = "flash",
                                   return_moe_stats: bool = False):
        """Slot-masked decode over the latent pool: ids [B, 1], pos [B]
        (each slot's own position). Returns (logits [B, V], pcache[,
        the tick's routing-load vector])."""
        impl = "ref" if mode == "xla" else "flash"
        pos = jnp.asarray(pos, jnp.int32)
        load = self._zero_load() if return_moe_stats else None
        x = self.embed[ids[:, 0]]
        cos, sin = self.cos[pos], self.sin[pos]    # once, for every layer
        planes = list(pcache.pages_k)
        for li, layer in enumerate(self.layers):
            u = rms_norm(x, layer.ln_attn, self.config.rms_norm_eps)
            a, planes[li] = layer.attn.decode(
                u, cos, sin, planes[li], pcache.table, pos, impl=impl)
            x, load = self._ffn(layer, x + a, mode, load)
        pcache = dataclasses.replace(pcache, pages_k=tuple(planes))
        if return_moe_stats:
            return self._logits(x), pcache, load
        return self._logits(x), pcache

    # -- admission: a whole prompt into one slot -----------------------

    def admit_slot_paged(self, ids, pcache, rows, slot, n,
                         mode: str = "flash"):
        """ids [1, P]: the prompt, zero-padded to its bucket; n: its
        real length; rows [maxp]: the slot's table row. Installs the
        row, writes the prompt's latent rows to the slot's pages
        (expanded attention over the prompt itself) and returns (logits
        [1, V] of its last token, pcache)."""
        impl = "ref" if mode == "xla" else "flash"
        page = pcache.page
        P_ = ids.shape[1]
        p = jnp.arange(P_)
        dest = jnp.where(
            p < n, rows[jnp.minimum(p // page, rows.shape[0] - 1)],
            pcache.trash)
        x = self.embed[ids[0]]
        cos, sin = self.cos[:P_], self.sin[:P_]
        planes = list(pcache.pages_k)
        for li, layer in enumerate(self.layers):
            u = rms_norm(x, layer.ln_attn, self.config.rms_norm_eps)
            a, planes[li] = layer.attn.prefill(
                u, cos, sin, planes[li], dest, p % page, impl=impl)
            x, _ = self._ffn(layer, x + a, mode, None)
        table = jax.lax.dynamic_update_slice(pcache.table, rows[None],
                                             (slot, 0))
        pcache = dataclasses.replace(pcache, pages_k=tuple(planes),
                                     table=table)
        last = jax.lax.dynamic_slice_in_dim(x, n - 1, 1, 0)
        return self._logits(last), pcache
