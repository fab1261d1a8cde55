"""Qwen3-MoE model (reference: `python/triton_dist/models/qwen_moe.py`
`Qwen3MoE:108` — Qwen3 attention blocks + routed-expert SwiGLU FFNs).

Functional pytree model mirroring DenseLLM; the FFN is either a TP_MoE
(experts replicated, intermediate sharded — the reference's TP-MoE
AG-GroupGEMM/MoE-reduce-RS path) or an EP_MoE (experts sharded, tokens
routed over ICI — the reference's EP a2a path), chosen at construction
(`moe_impl`), since the two shard the same weights differently.

Forward modes:
  "xla"      — oracle (dense all-experts MoE + psum attention).
  "flash"    — single-chip framework kernels (flash-decode + grouped
               GEMM expert dispatch).
  "dist"     — TP overlap kernels: AG-GEMM/GEMM-RS attention +
               AG-GroupGEMM + MoE-reduce-RS FFN (moe_impl="tp").
  "ep"       — AG-GEMM/GEMM-RS attention + EP dispatch/combine FFN
               (moe_impl="ep"); activations row-sharded end to end.
  "ep_flash" — framework attention kernels + EP dispatch/combine FFN
               (moe_impl="ep"): the EP SERVING mode on meshes whose
               attention rides "flash" (single chip, or the EP+TP
               hybrid below) — experts stay sharded and tokens still
               cross the a2a wire, without the comm-kernel attention.

SERVING (ISSUE 13 — the MoE paged serving subsystem): the model now
carries the FULL slot surface the continuous-batching scheduler
requires — `forward_tokens_slots` (+`_verify`),
`forward_tokens_slots_paged` (+`_verify`) — mirroring DenseLLM exactly:
attention layers are TP_Attn, so the paged/contiguous slot attends,
per-slot `kv_lens`+`q_lens` verify masks and the KV-head-group pool
split (PR 9) are REUSED unchanged; only the FFN differs — per-slot
top-k routing runs INSIDE the tick and the expert MLPs dispatch
through the grouped-GEMM kernel (kernels/group_gemm.py via
layers/tp_moe.py fwd_local, or the EP a2a path via layers/ep_moe.py).
`return_moe_stats=True` additionally returns the tick's routing-load
vector [expert_tokens[0..E-1], capacity_dropped] (int32 [E+1]) that
engine/scheduler surface as `expert_tokens{expert=...}` gauges,
`moe_capacity_drops` and `expert_load_imbalance` — the loud half of
dropless-or-loud, observable.

LEARNED SPARSE ATTENTION (`config.sa_config`, Keye-VL-2.0's language
model: this stack at its own numbers plus an indexer): the layers'
attention is `SA_Attn` (layers/sparse_attn.py), every query attending
the `topk` cached positions its indexer scores highest; the cache holds
K and V in one plane under the page table and the indexer's keys in a
per-slot plane beside it (kv_cache.IndexedSlotCache), which only this
model's programs read and write (`ServingTraits.own_pool`: it admits through
`admit_slot_paged` below, and whatever would move a slot through the
Engine's K/V page programs is refused by name); the experts are a
STATED SHARE (`config.held_experts`, `EP_MoE.fwd_share`): the chip
routes over all `num_experts` and adds what its own add. One chip,
paged serving only; `sa_config=None` is the stack as it was.

EP+TP HYBRID MESH: `moe_axis` names the mesh axis the experts shard
over (default: the attention `axis`). On a 2-D mesh like
make_mesh((2, 4), ("expert", "tp")), attention KV head-groups split on
"tp" exactly as PR 9 laid them out (the paged pool's G axis) while
expert panels and the a2a dispatch ride "expert" — one scheduler
drives the whole hybrid mesh through ONE sharded program per tick.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from triton_dist_tpu.layers import TP_Attn, precompute_rope, rms_norm
from triton_dist_tpu.layers.ep_moe import EP_MoE
from triton_dist_tpu.layers.sparse_attn import SA_Attn
from triton_dist_tpu.layers.tp_moe import TP_MoE
from triton_dist_tpu.models.config import ModelConfig
from triton_dist_tpu.models.kv_cache import KVCache
from triton_dist_tpu.models.utils import ServingTraits, place_replicated
from triton_dist_tpu.runtime import auto_mesh


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class MoELayer:
    attn: TP_Attn | SA_Attn
    moe: TP_MoE | EP_MoE
    ln_attn: jax.Array
    ln_mlp: jax.Array


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class Qwen3MoE:
    embed: jax.Array
    layers: Tuple[MoELayer, ...]
    final_norm: jax.Array
    lm_head: jax.Array
    cos: jax.Array
    sin: jax.Array
    config: ModelConfig = dataclasses.field(metadata=dict(static=True))
    mesh: Mesh = dataclasses.field(metadata=dict(static=True))
    axis: str = dataclasses.field(metadata=dict(static=True))
    moe_impl: str = dataclasses.field(default="tp",
                                      metadata=dict(static=True))
    # expert-parallel mesh axis (EP+TP hybrid serving): experts shard
    # over THIS axis while attention KV head-groups stay on `axis`.
    # None = same axis as attention (the single-axis meshes every
    # pre-hybrid caller builds).
    moe_axis: str = dataclasses.field(default=None,
                                      metadata=dict(static=True))
    # sparse attention only: the indexer's rotary tables [max_seq,
    # indexer_head_dim / 2]
    cos_i: jax.Array = None
    sin_i: jax.Array = None

    @property
    def ep_axis(self) -> str:
        """The mesh axis expert panels shard over."""
        return self.moe_axis or self.axis

    @property
    def ep_size(self) -> int:
        """Expert-parallel degree: rows fed to an EP FFN must divide by
        this (engine.make_*_cache validates the scheduler batch)."""
        if self.moe_impl != "ep":
            return 1
        return self.mesh.shape[self.ep_axis]

    # ------------------------------------------------------------------
    # construction
    # ------------------------------------------------------------------

    @staticmethod
    def random_init(cfg: ModelConfig, mesh: Mesh, axis: str = "tp",
                    seed: int = 0, moe_impl: str = "tp",
                    moe_axis: str = None,
                    capacity_factor=2.0) -> "Qwen3MoE":
        mesh = auto_mesh(mesh)
        key = jax.random.key(seed)
        D, I = cfg.hidden_size, cfg.moe_intermediate_size
        E, k = cfg.num_experts, cfg.num_experts_per_tok
        Hq, Hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        dt = cfg.jax_dtype
        kit = iter(jax.random.split(key, 65536))

        def w(*shape, scale=None):
            s = scale if scale is not None else (shape[-2] ** -0.5)
            return jax.random.normal(next(kit), shape,
                                     dtype=dt) * jnp.asarray(s, dtype=dt)

        if cfg.sa_config is not None:
            sa = cfg.sa_config
            Hi, di = sa.indexer_num_heads, sa.indexer_head_dim
            Eh = len(cfg.expert_ids)
            one = lambda n: jnp.ones((n,), dt)  # noqa: E731
            layers = [Qwen3MoE.make_sa_layer(cfg, dict(
                wq=w(D, Hq * hd), wk=w(D, Hkv * hd), wv=w(D, Hkv * hd),
                wo=w(Hq * hd, D), q_norm=one(hd), k_norm=one(hd),
                w_qi=w(D, Hi * di), w_ki=w(D, di), w_w=w(D, Hi),
                w_router=w(D, E, scale=0.02), we_gate=w(Eh, D, I),
                we_up=w(Eh, D, I), we_down=w(Eh, I, D), ln_attn=one(D),
                ln_mlp=one(D)), mesh, axis)
                for _ in range(cfg.num_layers)]
            head = {"embed": w(cfg.vocab_size, D, scale=0.02),
                    "final_norm": one(D),
                    "lm_head": w(D, cfg.vocab_size, scale=0.02)}
            return Qwen3MoE.build_sa(cfg, head, layers, mesh, axis)

        moe_cls = TP_MoE if moe_impl == "tp" else EP_MoE
        layers = []
        for _ in range(cfg.num_layers):
            attn = TP_Attn.init(
                w(D, Hq * hd), w(D, Hkv * hd), w(D, Hkv * hd),
                w(Hq * hd, D), mesh=mesh, axis=axis, n_heads=Hq,
                n_kv_heads=Hkv, head_dim=hd,
                q_norm=np.ones(hd, np.float32),
                k_norm=np.ones(hd, np.float32))
            moe = moe_cls.init(
                w(D, E, scale=0.02), w(E, D, I), w(E, D, I), w(E, I, D),
                mesh=mesh, axis=moe_axis or axis, top_k=k,
                capacity_factor=capacity_factor)
            layers.append(MoELayer(
                attn=attn, moe=moe,
                ln_attn=jnp.ones((D,), dt), ln_mlp=jnp.ones((D,), dt)))
        cos, sin = precompute_rope(hd, cfg.max_position_embeddings,
                                   cfg.rope_theta)
        embed = w(cfg.vocab_size, D, scale=0.02)
        model = Qwen3MoE(
            embed=embed, layers=tuple(layers),
            final_norm=jnp.ones((D,), dt),
            lm_head=(embed.T if cfg.tie_word_embeddings
                     else w(D, cfg.vocab_size, scale=0.02)),
            cos=cos, sin=sin, config=cfg, mesh=mesh, axis=axis,
            moe_impl=moe_impl, moe_axis=moe_axis)
        return place_replicated(model, mesh)

    @staticmethod
    def from_hf(path: str, mesh: Mesh, axis: str = "tp",
                moe_impl: str = "tp", moe_axis: str = None,
                capacity_factor=2.0) -> "Qwen3MoE":
        """Load HF Qwen3-MoE safetensors, stacking per-expert projections
        (reference: models/qwen_moe.py HF loading + TP shard at load)."""
        from safetensors import safe_open

        mesh = auto_mesh(mesh)
        cfg = ModelConfig.from_hf_config(path)
        Hq, Hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        dt = cfg.jax_dtype
        tensors = {}
        for fn in sorted(os.listdir(path)):
            if fn.endswith(".safetensors"):
                with safe_open(os.path.join(path, fn), framework="np") as f:
                    for key in f.keys():
                        tensors[key] = f.get_tensor(key)

        def t(name):
            return jnp.asarray(tensors[name], dtype=dt)

        moe_cls = TP_MoE if moe_impl == "tp" else EP_MoE
        layers = []
        for li in range(cfg.num_layers):
            p = f"model.layers.{li}."
            attn = TP_Attn.init(
                t(p + "self_attn.q_proj.weight").T,
                t(p + "self_attn.k_proj.weight").T,
                t(p + "self_attn.v_proj.weight").T,
                t(p + "self_attn.o_proj.weight").T,
                mesh=mesh, axis=axis, n_heads=Hq, n_kv_heads=Hkv,
                head_dim=hd,
                q_norm=tensors.get(p + "self_attn.q_norm.weight"),
                k_norm=tensors.get(p + "self_attn.k_norm.weight"))
            gate = jnp.stack([
                t(p + f"mlp.experts.{e}.gate_proj.weight").T
                for e in range(cfg.num_experts)])
            up = jnp.stack([
                t(p + f"mlp.experts.{e}.up_proj.weight").T
                for e in range(cfg.num_experts)])
            down = jnp.stack([
                t(p + f"mlp.experts.{e}.down_proj.weight").T
                for e in range(cfg.num_experts)])
            moe = moe_cls.init(
                t(p + "mlp.gate.weight").T, gate, up, down,
                mesh=mesh, axis=moe_axis or axis,
                top_k=cfg.num_experts_per_tok,
                capacity_factor=capacity_factor)
            layers.append(MoELayer(
                attn=attn, moe=moe,
                ln_attn=t(p + "input_layernorm.weight"),
                ln_mlp=t(p + "post_attention_layernorm.weight")))
        cos, sin = precompute_rope(hd, cfg.max_position_embeddings,
                                   cfg.rope_theta)
        embed = t("model.embed_tokens.weight")
        model = Qwen3MoE(
            embed=embed, layers=tuple(layers),
            final_norm=t("model.norm.weight"),
            lm_head=(embed.T if cfg.tie_word_embeddings
                     else t("lm_head.weight").T),
            cos=cos, sin=sin, config=cfg, mesh=mesh, axis=axis,
            moe_impl=moe_impl, moe_axis=moe_axis)
        return place_replicated(model, mesh)

    # ------------------------------------------------------------------
    # forward (mirrors DenseLLM.forward_tokens)
    # ------------------------------------------------------------------

    def _moe_modes(self, mode: str):
        """(attention mode, FFN mode) for one model-level mode string.
        "ep" pairs the comm-kernel attention (AG-GEMM/GEMM-RS) with the
        EP dispatch; "ep_flash" pairs the framework attention kernels
        with the SAME EP dispatch — the serving spelling for meshes
        whose attention path is "flash" (single chip / hybrid EP+TP).
        Every other mode runs the EP model's FFN through the dense
        all-experts oracle (the differential-test arm)."""
        attn_mode = ("dist" if mode == "ep" else
                     "flash" if mode == "ep_flash" else mode)
        if self.moe_impl == "ep":
            moe_mode = "ep" if mode in ("ep", "ep_flash") else "xla"
        else:
            moe_mode = "dist" if mode in ("ep", "ep_flash") else mode
        return attn_mode, moe_mode

    def _zero_load(self):
        """Fresh routing-load accumulator: [expert_tokens[0..E-1],
        capacity_dropped] — the serving tick's telemetry payload. A
        stated share with sparse attention reports its held experts'
        counts and six entries more (`_sa_ffn`)."""
        if self.config.sa_config is not None:
            return jnp.zeros((len(self.config.expert_ids) + 7,), jnp.int32)
        return jnp.zeros((self.config.num_experts + 1,), jnp.int32)

    def _moe_ffn(self, layer, h, moe_mode, load):
        """One routed FFN call; accumulates the routing-load vector
        when the caller asked for stats (load is None otherwise)."""
        if load is None:
            return layer.moe(h, moe_mode), None
        y, st = layer.moe(h, moe_mode, return_stats=True)
        upd = jnp.concatenate([st["expert_tokens"],
                               st["dropped"].reshape(1)])
        return y, load + upd

    def forward_tokens(self, ids, cache: KVCache, mode: str = "dist",
                       last_pos=None):
        B, S = ids.shape
        attn_mode, moe_mode = self._moe_modes(mode)
        x = self.embed[ids].reshape(B * S, self.config.hidden_size)
        kv_start = cache.offset
        for li, layer in enumerate(self.layers):
            kv = cache.layer(li)
            h = rms_norm(x, layer.ln_attn, self.config.rms_norm_eps)
            a, kv = layer.attn.fwd_cached(
                h, self.cos, self.sin, B, kv, kv_start, attn_mode)
            cache = cache.set_layer(li, kv)
            x = x + a
            h = rms_norm(x, layer.ln_mlp, self.config.rms_norm_eps)
            x = x + layer.moe(h, moe_mode).astype(x.dtype)
        cache = cache.advance(S)
        x = rms_norm(x, self.final_norm, self.config.rms_norm_eps)
        if mode in ("dist", "ep"):
            x = self._gather_rows(x)
        xr = x.reshape(B, S, -1)
        last = xr[:, -1] if last_pos is None else jnp.take(
            xr, last_pos, axis=1)
        logits = jnp.dot(last, self.lm_head,
                         preferred_element_type=jnp.float32)
        return logits, cache

    def forward_tokens_slots(self, ids, cache: KVCache, pos,
                             mode: str = "dist",
                             return_moe_stats: bool = False):
        """Slot-masked decode forward (continuous batching; mirrors
        DenseLLM.forward_tokens_slots): ids [B, 1], pos [B] int32 —
        row b decodes at its own position. cache.offset is untouched.
        return_moe_stats=True appends the tick's routing-load vector
        (engine/scheduler telemetry — see the module docstring)."""
        B, S = ids.shape
        assert S == 1, "slot decode feeds one token per slot"
        attn_mode, moe_mode = self._moe_modes(mode)
        load = self._zero_load() if return_moe_stats else None
        x = self.embed[ids].reshape(B, self.config.hidden_size)
        for li, layer in enumerate(self.layers):
            kv = cache.layer(li)
            h = rms_norm(x, layer.ln_attn, self.config.rms_norm_eps)
            a, kv = layer.attn.fwd_cached_slots(
                h, self.cos, self.sin, B, kv, pos, attn_mode)
            cache = cache.set_layer(li, kv)
            x = x + a
            h = rms_norm(x, layer.ln_mlp, self.config.rms_norm_eps)
            y, load = self._moe_ffn(layer, h, moe_mode, load)
            x = x + y.astype(x.dtype)
        x = rms_norm(x, self.final_norm, self.config.rms_norm_eps)
        if mode in ("dist", "ep"):
            x = self._gather_rows(x)
        logits = jnp.dot(x, self.lm_head,
                         preferred_element_type=jnp.float32)
        if return_moe_stats:
            return logits, cache, load
        return logits, cache

    def forward_tokens_slots_verify(self, ids, cache: KVCache, pos,
                                    q_lens, mode: str = "dist",
                                    return_moe_stats: bool = False):
        """Speculative-verify forward over the CONTIGUOUS slot cache
        (mirrors DenseLLM.forward_tokens_slots_verify): each batch row
        scores a variable-length draft window in ONE pass via the
        per-slot `q_lens`+`kv_lens` masks — the PR-3 machinery, reused
        byte-for-byte since attention layers are TP_Attn. The routed
        FFN sees the window rows exactly like decode rows (padded rows
        are computed-and-discarded; their routed entries count toward
        the load gauges — compute load, not emitted tokens)."""
        B, S = ids.shape
        attn_mode, moe_mode = self._moe_modes(mode)
        load = self._zero_load() if return_moe_stats else None
        x = self.embed[ids].reshape(B * S, self.config.hidden_size)
        for li, layer in enumerate(self.layers):
            kv = cache.layer(li)
            h = rms_norm(x, layer.ln_attn, self.config.rms_norm_eps)
            a, kv = layer.attn.fwd_cached_slots_verify(
                h, self.cos, self.sin, B, kv, pos, q_lens, attn_mode)
            cache = cache.set_layer(li, kv)
            x = x + a
            h = rms_norm(x, layer.ln_mlp, self.config.rms_norm_eps)
            y, load = self._moe_ffn(layer, h, moe_mode, load)
            x = x + y.astype(x.dtype)
        x = rms_norm(x, self.final_norm, self.config.rms_norm_eps)
        if mode in ("dist", "ep"):
            x = self._gather_rows(x)
        logits = jnp.dot(x, self.lm_head,
                         preferred_element_type=jnp.float32)
        if return_moe_stats:
            return logits.reshape(B, S, -1), cache, load
        return logits.reshape(B, S, -1), cache

    def forward_tokens_slots_paged(self, ids, pcache, pos,
                                   mode: str = "flash",
                                   return_moe_stats: bool = False):
        """Slot-masked decode forward over the PAGED KV pool (mirrors
        DenseLLM.forward_tokens_slots_paged — the shared-prefix serving
        tick): identical attention math through the page table (slot b
        attends whatever pages its table row maps, including pages
        shared read-only with other slots' cached prefixes), with
        PER-SLOT TOP-K ROUTING inside the tick and grouped-GEMM expert
        dispatch replacing the per-expert dense loop. ids [B, 1];
        pos [B] int32; pcache: PagedSlotCache."""
        B, S = ids.shape
        assert S == 1, "slot decode feeds one token per slot"
        if self.config.sa_config is not None:
            return self._sa_decode(ids, pcache, pos, mode,
                                   return_moe_stats)
        attn_mode, moe_mode = self._moe_modes(mode)
        load = self._zero_load() if return_moe_stats else None
        x = self.embed[ids].reshape(B, self.config.hidden_size)
        for li, layer in enumerate(self.layers):
            h = rms_norm(x, layer.ln_attn, self.config.rms_norm_eps)
            a, kv = layer.attn.fwd_cached_slots_paged(
                h, self.cos, self.sin, B, pcache.layer(li),
                pcache.table, pos, attn_mode)
            pcache = pcache.set_layer(li, *kv)
            x = x + a
            h = rms_norm(x, layer.ln_mlp, self.config.rms_norm_eps)
            y, load = self._moe_ffn(layer, h, moe_mode, load)
            x = x + y.astype(x.dtype)
        x = rms_norm(x, self.final_norm, self.config.rms_norm_eps)
        if mode in ("dist", "ep"):
            x = self._gather_rows(x)
        logits = jnp.dot(x, self.lm_head,
                         preferred_element_type=jnp.float32)
        if return_moe_stats:
            return logits, pcache, load
        return logits, pcache

    def forward_tokens_slots_paged_verify(self, ids, pcache, pos,
                                          q_lens, mode: str = "flash",
                                          return_moe_stats: bool = False):
        """forward_tokens_slots_verify over the PAGED pool (mirrors the
        dense twin): the draft window's K/V resolves through the page
        table (padded rows scatter out of bounds and are dropped) and
        attention walks the pool with per-slot kv_lens AND q_lens; the
        routed FFN dispatches the whole mixed window through the
        grouped GEMMs. This is ALSO the chunked-prefill mixed tick's
        forward (engine._mixed_forward) — prefill chunk rows route
        through the experts alongside live decode rows."""
        B, S = ids.shape
        attn_mode, moe_mode = self._moe_modes(mode)
        load = self._zero_load() if return_moe_stats else None
        x = self.embed[ids].reshape(B * S, self.config.hidden_size)
        for li, layer in enumerate(self.layers):
            h = rms_norm(x, layer.ln_attn, self.config.rms_norm_eps)
            a, kv = layer.attn.fwd_cached_slots_paged_verify(
                h, self.cos, self.sin, B, pcache.layer(li),
                pcache.table, pos, q_lens, attn_mode)
            pcache = pcache.set_layer(li, *kv)
            x = x + a
            h = rms_norm(x, layer.ln_mlp, self.config.rms_norm_eps)
            y, load = self._moe_ffn(layer, h, moe_mode, load)
            x = x + y.astype(x.dtype)
        x = rms_norm(x, self.final_norm, self.config.rms_norm_eps)
        if mode in ("dist", "ep"):
            x = self._gather_rows(x)
        logits = jnp.dot(x, self.lm_head,
                         preferred_element_type=jnp.float32)
        if return_moe_stats:
            return logits.reshape(B, S, -1), pcache, load
        return logits.reshape(B, S, -1), pcache

    def forward_train(self, ids, mode: str = "train"):
        """Training forward (no KV cache), mirroring
        DenseLLM.forward_train: full-causal attention, all-position
        logits [B, S, V].

        mode="train": attention through the custom-VJP ag_gemm/gemm_rs +
        Pallas flash kernels; the MoE FFN through custom-VJP
        all_gather/grouped-GEMM/reduce_scatter (moe_impl="tp",
        layers/tp_moe.py::fwd_train) or custom-VJP a2a dispatch/combine
        + grouped GEMMs (moe_impl="ep", layers/ep_moe.py::fwd_train) —
        the reference's autograd Function over the fused MoE ops,
        function/nvidia/ep_moe_fused.py:42.
        mode="xla": the dense all-experts oracle for gradient tests.
        """
        B, S = ids.shape
        impl = "flash" if mode == "train" else "ref"
        moe_mode = "train" if mode == "train" else "xla"
        x = self.embed[ids].reshape(B * S, self.config.hidden_size)
        for layer in self.layers:
            h = rms_norm(x, layer.ln_attn, self.config.rms_norm_eps)
            x = x + layer.attn.fwd_train(h, self.cos, self.sin, B, impl)
            h = rms_norm(x, layer.ln_mlp, self.config.rms_norm_eps)
            x = x + layer.moe(h, moe_mode).astype(x.dtype)
        x = rms_norm(x, self.final_norm, self.config.rms_norm_eps)
        if mode == "train":
            x = self._gather_rows(x)
        logits = jnp.dot(x, self.lm_head,
                         preferred_element_type=jnp.float32)
        return logits.reshape(B, S, -1)

    def _gather_rows(self, x):
        """Row-sharded [M, D] -> replicated (the LM-head prologue; same
        helper as DenseLLM._gather_rows)."""
        import functools

        @functools.partial(
            jax.shard_map, mesh=self.mesh,
            in_specs=P(self.axis, None), out_specs=P(None, None),
            check_vma=False)
        def gather_rows(x_loc):
            return jax.lax.all_gather(x_loc, self.axis, axis=0,
                                      tiled=True)

        return gather_rows(x)

    def make_cache(self, batch: int, max_seq: int, dtype=None) -> KVCache:
        cfg = self.config
        return KVCache.create(cfg.num_layers, batch, max_seq,
                              cfg.num_kv_heads, cfg.head_dim,
                              mesh=self.mesh, axis=self.axis,
                              dtype=dtype or cfg.jax_dtype)

    def serving_traits(self):
        return ServingTraits(
            kv_heads=self.config.num_kv_heads,
            own_pool=(None if self.config.sa_config is None
                      else "K/V and index-key planes"))

    def make_paged_cache(self, batch: int, max_seq: int, **kw):
        if self.config.sa_config is not None:
            from triton_dist_tpu.models.kv_cache import IndexedSlotCache
            cfg = self.config
            return IndexedSlotCache.create_indexed(
                cfg.num_layers, batch, max_seq,
                n_kv_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
                index_dim=cfg.sa_config.indexer_head_dim, page=kw["page"],
                num_pages=kw["num_pages"], mesh=self.mesh,
                dtype=kw.get("dtype") or cfg.jax_dtype)
        from triton_dist_tpu.models.kv_cache import uniform_paged_cache
        return uniform_paged_cache(self, batch, max_seq, **kw)

    # ------------------------------------------------------------------
    # learned sparse attention (config.sa_config): construction, the
    # decode tick and the admission over the K/V pool and the per-slot
    # index plane
    # ------------------------------------------------------------------

    @staticmethod
    def make_sa_layer(cfg: ModelConfig, w: dict, mesh: Mesh,
                      axis: str = "tp") -> MoELayer:
        """One layer from a dict of plain arrays under the reference's
        names (benchmark/reference/keye_vl2.py `_layer_weights`); its
        `we_*` hold the HELD experts only."""
        mesh = _one_chip(mesh, axis)
        sa = cfg.sa_config
        attn = SA_Attn.init(
            w["wq"], w["wk"], w["wv"], w["wo"], w["q_norm"], w["k_norm"],
            w["w_qi"], w["w_ki"], w["w_w"], n_heads=cfg.num_heads,
            n_kv_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
            idx_heads=sa.indexer_num_heads, idx_dim=sa.indexer_head_dim,
            topk=sa.topk, sections=cfg.mrope_section,
            eps=cfg.rms_norm_eps)
        ids = cfg.expert_ids
        moe = EP_MoE.init(
            w["w_router"], w["we_gate"], w["we_up"], w["we_down"],
            mesh=mesh, axis=axis, top_k=cfg.num_experts_per_tok,
            capacity_factor="dropless", held=(ids[0], len(ids)))
        return MoELayer(attn=attn, moe=moe, ln_attn=w["ln_attn"],
                        ln_mlp=w["ln_mlp"])

    @staticmethod
    def build_sa(cfg: ModelConfig, head: dict, layers, mesh: Mesh,
                 axis: str = "tp") -> "Qwen3MoE":
        """head: {"embed", "final_norm", "lm_head"}; layers from
        `make_sa_layer`. One chip: the mesh's `axis` has size 1."""
        mesh = _one_chip(mesh, axis)
        cos, sin = precompute_rope(cfg.head_dim,
                                   cfg.max_position_embeddings,
                                   cfg.rope_theta)
        cos_i, sin_i = precompute_rope(cfg.sa_config.indexer_head_dim,
                                       cfg.max_position_embeddings,
                                       cfg.rope_theta)
        model = Qwen3MoE(
            embed=head["embed"], layers=tuple(layers),
            final_norm=head["final_norm"], lm_head=head["lm_head"],
            cos=cos, sin=sin, config=cfg, mesh=mesh, axis=axis,
            moe_impl="ep", cos_i=cos_i, sin_i=sin_i)
        return place_replicated(model, mesh)

    def _sa_ffn(self, layer, x, load=None, context=None, attended=None):
        """x + the held experts' part of FFN(RMSNorm(x)); `load`
        accumulates [expert_tokens of the held experts, dropped (0),
        pairs routed, pairs held, positions in context, positions
        attended, held experts with a pair, held experts] when the
        caller asked for it."""
        h = rms_norm(x, layer.ln_mlp, self.config.rms_norm_eps)
        y, st = layer.moe.fwd_share(h, return_stats=True)
        if load is not None:
            load = load + jnp.concatenate([
                st["expert_tokens"], st["dropped"].reshape(1),
                st["pairs_routed"].reshape(1), st["pairs_held"].reshape(1),
                context.reshape(1), attended.reshape(1),
                jnp.sum(st["expert_tokens"] > 0).reshape(1),
                jnp.full((1,), st["expert_tokens"].shape[0])
            ]).astype(jnp.int32)
        return x + y.astype(x.dtype), load

    def _sa_logits(self, x):
        x = rms_norm(x, self.final_norm, self.config.rms_norm_eps)
        return jnp.dot(x, self.lm_head, preferred_element_type=jnp.float32)

    def _sa_decode(self, ids, pcache, pos, mode, return_moe_stats):
        impl = "ref" if mode == "xla" else "flash"
        pos = jnp.asarray(pos, jnp.int32)
        load = self._zero_load() if return_moe_stats else None
        x = self.embed[ids[:, 0]]
        # the tables' rows once, for every layer
        rope = self.layers[0].attn.rope_of(self.cos, self.sin, self.cos_i,
                                           self.sin_i, pos)
        kv, ix = list(pcache.pages_k), list(pcache.pages_i)
        for li, layer in enumerate(self.layers):
            u = rms_norm(x, layer.ln_attn, self.config.rms_norm_eps)
            a, kv[li], ix[li], n_att = layer.attn.decode(
                u, *rope, kv[li], ix[li], pcache.table, pos, impl=impl)
            x, load = self._sa_ffn(layer, x + a, load, jnp.sum(pos + 1),
                                   jnp.sum(n_att))
        pcache = dataclasses.replace(pcache, pages_k=tuple(kv),
                                     pages_i=tuple(ix))
        if return_moe_stats:
            return self._sa_logits(x), pcache, load
        return self._sa_logits(x), pcache

    def admit_slot_paged(self, ids, pcache, rows, slot, n,
                         mode: str = "flash"):
        """Sparse attention only (the Engine's own admission serves the
        other stacks). ids [1, P]: the prompt, zero-padded to its
        bucket; n: its real length; rows [maxp]: the slot's table row.
        Installs the row, writes the prompt's K/V rows to the slot's
        pages and its index keys to the slot's run of the index plane,
        and returns (logits [1, V] of its last token,
        pcache)."""
        impl = "ref" if mode == "xla" else "flash"
        page = pcache.page
        P_ = ids.shape[1]
        npg = -(-P_ // page)
        # the prompt's pages; the trash page for one wholly past its end
        pids = jnp.where(jnp.arange(npg) * page < n, rows[:npg],
                         pcache.trash)
        x = self.embed[ids[0]]
        rope = self.layers[0].attn.rope_of(self.cos, self.sin, self.cos_i,
                                           self.sin_i, jnp.arange(P_))
        kv, ix = list(pcache.pages_k), list(pcache.pages_i)
        for li, layer in enumerate(self.layers):
            u = rms_norm(x, layer.ln_attn, self.config.rms_norm_eps)
            a, kv[li], ix[li] = layer.attn.prefill(
                u, *rope, kv[li], ix[li], pids, slot, impl=impl)
            x, _ = self._sa_ffn(layer, x + a)
        table = jax.lax.dynamic_update_slice(pcache.table, rows[None],
                                             (slot, 0))
        pcache = dataclasses.replace(pcache, pages_k=tuple(kv),
                                     pages_i=tuple(ix), table=table)
        last = jax.lax.dynamic_slice_in_dim(x, n - 1, 1, 0)
        return self._sa_logits(last), pcache


def _one_chip(mesh: Mesh, axis: str) -> Mesh:
    mesh = auto_mesh(mesh)
    if mesh.shape[axis] != 1:
        raise ValueError(
            f"Qwen3MoE with sa_config serves one chip's share (mesh axis "
            f"{axis!r} has size {mesh.shape[axis]}); missing capability: "
            "tensor-parallel sparse attention (the indexer's one key "
            "head has nothing to split) and the expert exchange across "
            "a mesh")
    return mesh
