"""Dense Qwen3-family LLM (reference: `python/triton_dist/models/dense.py`
`DenseLLM:117`, per-layer `set_fwd` mode switch :84-100, TP context init
:169-209; HF weight loading + TP sharding at load :150-168).

Functional pytree model: weights are leaves, mode is an argument (the
reference mutates per-layer fwd pointers; here the mode string selects
the path inside one jitted function — same switch, jit-compatible).
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from triton_dist_tpu.layers import TP_Attn, TP_MLP, precompute_rope, rms_norm
from triton_dist_tpu.models.config import ModelConfig
from triton_dist_tpu.models.kv_cache import KVCache
from triton_dist_tpu.models.utils import (ServingTraits, place_replicated,
                                         split_last_axis)
from triton_dist_tpu.runtime import auto_mesh


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class DenseLayer:
    attn: TP_Attn
    mlp: TP_MLP
    ln_attn: jax.Array
    ln_mlp: jax.Array


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class DenseLLM:
    """PLACEMENT over `mesh`: the TP layers split their own projections
    over `axis`; the LM head's VOCABULARY columns are split over the
    same axis wherever it has more than one chip and divides the
    vocabulary (`vocab_axis`), so a decode step reads V/n columns of
    the head a chip and not all of it; everything else (embedding,
    norms, rope tables) is replicated. `place_replicated` places all of
    it, at the end of every constructor. Downstream of a split head
    the logits [.., V] stay split over the vocabulary (`_head` pins
    them): the engine's carry, the grammar mask and the greedy pick
    all work on a chip's own columns, and only a token id a slot
    crosses chips."""
    embed: jax.Array            # [V, D]
    layers: Tuple[DenseLayer, ...]
    final_norm: jax.Array       # [D]
    lm_head: jax.Array          # [D, V]; columns over vocab_axis
    cos: jax.Array
    sin: jax.Array
    config: ModelConfig = dataclasses.field(metadata=dict(static=True))
    mesh: Mesh = dataclasses.field(metadata=dict(static=True))
    axis: str = dataclasses.field(metadata=dict(static=True))
    # SEQUENCE-PARALLEL serving (long-context — kv_cache.PagedSlotCache
    # SP SHARDING): the mesh axis the paged pool's page-id space
    # shards over (None = single-chip pools). The paged slot forwards
    # then attend through the split-KV partial + cross-chip LSE
    # combine (layers/tp_attn.py fwd_cached_slots_paged_sp);
    # sp_combine picks the merge ("xla" = all_gather + lse_combine,
    # "dist" = the one-sided Pallas push kernel of
    # kernels/sp_flash_decode.py).
    sp_axis: Optional[str] = dataclasses.field(
        default=None, metadata=dict(static=True))
    sp_combine: str = dataclasses.field(
        default="xla", metadata=dict(static=True))

    @property
    def sp_size(self) -> int:
        """Sequence-parallel mesh size (1 = no page sharding)."""
        return self.mesh.shape[self.sp_axis] if self.sp_axis else 1

    @property
    def vocab_axis(self) -> Optional[str]:
        """The mesh axis the LM head's vocabulary columns (and the
        logits after it) are split over: the TP axis where it has more
        than one chip and divides the vocabulary, else None (a head
        replicated, as on one chip)."""
        n = self.mesh.shape[self.axis]
        return self.axis if n > 1 and self.config.vocab_size % n == 0 \
            else None

    def split_leaves(self) -> dict:
        """{field: mesh axis} of the leaves that are NOT replicated
        over the mesh but split along their LAST dimension (what
        place_replicated asks a model): the head's [D, V], and both
        leaves of its int8 form, q [D, V] and s [V]."""
        ax = self.vocab_axis
        return {"lm_head": ax} if ax else {}

    # ------------------------------------------------------------------
    # construction
    # ------------------------------------------------------------------

    @staticmethod
    def random_init(cfg: ModelConfig, mesh: Mesh, axis: str = "tp",
                    seed: int = 0, sp_axis: Optional[str] = None,
                    sp_combine: str = "xla") -> "DenseLLM":
        """Random weights with Qwen3 shapes — the harness/test model.
        Generated device-side (jax.random): host-numpy generation of
        billion-parameter models takes minutes on one core.

        sp_axis: mesh axis for SEQUENCE-PARALLEL paged serving (the
        long-context layout — weights replicate over it, only the
        paged pool shards; build the mesh as e.g.
        jax.make_mesh((1, 4), ("tp", "sp")) and pass sp_axis="sp")."""
        mesh = auto_mesh(mesh)
        key = jax.random.key(seed)
        D, I = cfg.hidden_size, cfg.intermediate_size
        Hq, Hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        dt = cfg.jax_dtype
        kit = iter(jax.random.split(key, 16384))

        def w(*shape, scale=None):
            s = scale if scale is not None else (shape[0] ** -0.5)
            return jax.random.normal(next(kit), shape, dtype=dt) * jnp.asarray(
                s, dtype=dt)

        layers = []
        for _ in range(cfg.num_layers):
            attn = TP_Attn.init(
                w(D, Hq * hd), w(D, Hkv * hd), w(D, Hkv * hd),
                w(Hq * hd, D), mesh=mesh, axis=axis, n_heads=Hq,
                n_kv_heads=Hkv, head_dim=hd,
                q_norm=np.ones(hd, np.float32),
                k_norm=np.ones(hd, np.float32))
            mlp = TP_MLP.init(w(D, I), w(D, I), w(I, D), mesh=mesh,
                              axis=axis)
            layers.append(DenseLayer(
                attn=attn, mlp=mlp,
                ln_attn=jnp.ones((D,), dt), ln_mlp=jnp.ones((D,), dt)))
        cos, sin = precompute_rope(hd, cfg.max_position_embeddings,
                                   cfg.rope_theta)
        embed = w(cfg.vocab_size, D, scale=0.02)
        model = DenseLLM(
            embed=embed, layers=tuple(layers),
            final_norm=jnp.ones((D,), dt),
            lm_head=(embed.T if cfg.tie_word_embeddings
                     else w(D, cfg.vocab_size, scale=0.02)),
            cos=cos, sin=sin, config=cfg, mesh=mesh, axis=axis,
            sp_axis=sp_axis, sp_combine=sp_combine)
        return place_replicated(model, mesh)

    @staticmethod
    def from_hf(path: str, mesh: Mesh, axis: str = "tp",
                sp_axis: Optional[str] = None,
                sp_combine: str = "xla") -> "DenseLLM":
        """Load HF Qwen3 safetensors and shard at load (reference:
        models/dense.py:150-168). Requires a local checkpoint dir."""
        from safetensors import safe_open

        mesh = auto_mesh(mesh)
        cfg = ModelConfig.from_hf_config(path)
        D, Hq, Hkv, hd = (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads,
                          cfg.head_dim)
        dt = cfg.jax_dtype
        tensors = {}
        for fn in sorted(os.listdir(path)):
            if fn.endswith(".safetensors"):
                with safe_open(os.path.join(path, fn), framework="np") as f:
                    for key in f.keys():
                        tensors[key] = f.get_tensor(key)

        def t(name):
            return jnp.asarray(tensors[name], dtype=dt)

        layers = []
        for li in range(cfg.num_layers):
            p = f"model.layers.{li}."
            # HF stores projections transposed ([out, in])
            attn = TP_Attn.init(
                t(p + "self_attn.q_proj.weight").T,
                t(p + "self_attn.k_proj.weight").T,
                t(p + "self_attn.v_proj.weight").T,
                t(p + "self_attn.o_proj.weight").T,
                mesh=mesh, axis=axis, n_heads=Hq, n_kv_heads=Hkv,
                head_dim=hd,
                q_norm=tensors.get(p + "self_attn.q_norm.weight"),
                k_norm=tensors.get(p + "self_attn.k_norm.weight"))
            mlp = TP_MLP.init(
                t(p + "mlp.gate_proj.weight").T,
                t(p + "mlp.up_proj.weight").T,
                t(p + "mlp.down_proj.weight").T, mesh=mesh, axis=axis)
            layers.append(DenseLayer(
                attn=attn, mlp=mlp,
                ln_attn=t(p + "input_layernorm.weight"),
                ln_mlp=t(p + "post_attention_layernorm.weight")))
        cos, sin = precompute_rope(hd, cfg.max_position_embeddings,
                                   cfg.rope_theta)
        embed = t("model.embed_tokens.weight")
        lm_head = (embed.T if cfg.tie_word_embeddings
                   else t("lm_head.weight").T)
        model = DenseLLM(embed=embed, layers=tuple(layers),
                         final_norm=t("model.norm.weight"),
                         lm_head=lm_head, cos=cos, sin=sin, config=cfg,
                         mesh=mesh, axis=axis, sp_axis=sp_axis,
                         sp_combine=sp_combine)
        return place_replicated(model, mesh)

    def quantize_int8(self) -> "DenseLLM":
        """Weight-only int8 copy for the bandwidth-bound decode regime
        (kernels/quant.py): projection weights and the lm_head become
        QuantW (int8 + per-column scale), halving the per-step weight
        read. Valid for EVERY forward mode: "flash"/"xla" dequant via
        qmm, and the comm-kernel modes ("dist"/"ar"/"gemm_ar") stream
        int8 weight panels through ag_gemm/gemm_rs/gemm_allreduce with
        the per-column dequant fused after each dot (exact). Embed
        stays bf16 (it is a gather, not a GEMM)."""
        from triton_dist_tpu.kernels.quant import quantize_int8 as q8
        layers = tuple(
            dataclasses.replace(
                ly,
                attn=dataclasses.replace(ly.attn, w_qkv=q8(ly.attn.w_qkv),
                                         w_o=q8(ly.attn.w_o)),
                mlp=dataclasses.replace(ly.mlp,
                                        w_gate_up=q8(ly.mlp.w_gate_up),
                                        w_down=q8(ly.mlp.w_down)))
            for ly in self.layers)
        # the int8 head's leaves are placed as the bf16 head was
        return place_replicated(
            dataclasses.replace(self, layers=layers,
                                lm_head=q8(self.lm_head)), self.mesh)

    # ------------------------------------------------------------------
    # forward
    # ------------------------------------------------------------------

    def forward_tokens(self, ids, cache: KVCache, mode: str = "dist",
                       mlp_mode: Optional[str] = None, last_pos=None):
        """One forward pass over `ids` [B, S] starting at cache.offset;
        fills the cache and returns (last-position logits [B, V], cache).

        mode: attention forward mode; mlp_mode defaults to mode. For
        "dist", B*S must be divisible by the TP size (reference contract:
        max_M-padded symmetric workspaces, allgather_gemm.py:447).

        last_pos: optional traced scalar — take the logits at THIS
        sequence position instead of S-1 (the bucketed prefill-into-slot
        path pads prompts to a fixed S and reads the last REAL position,
        engine.prefill_into_slot).
        """
        B, S = ids.shape
        mlp_mode = mlp_mode or mode
        x = self.embed[ids].reshape(B * S, self.config.hidden_size)
        kv_start = cache.offset
        for li, layer in enumerate(self.layers):
            kv = cache.layer(li)
            h = rms_norm(x, layer.ln_attn, self.config.rms_norm_eps)
            a, kv = layer.attn.fwd_cached(
                h, self.cos, self.sin, B, kv, kv_start, mode)
            cache = cache.set_layer(li, kv)
            x = x + a
            h = rms_norm(x, layer.ln_mlp, self.config.rms_norm_eps)
            x = x + layer.mlp(h, mlp_mode)
        cache = cache.advance(S)
        x = rms_norm(x, self.final_norm, self.config.rms_norm_eps)
        if mode == "dist":
            # activations are row-sharded; gather for the LM head tail
            x = self._gather_rows(x)
        xr = x.reshape(B, S, -1)
        last = xr[:, -1] if last_pos is None else jnp.take(
            xr, last_pos, axis=1)
        return self._head(last), cache

    def forward_tokens_slots(self, ids, cache: KVCache, pos,
                             mode: str = "dist",
                             mlp_mode: Optional[str] = None):
        """Slot-masked decode forward (continuous batching): one token
        per batch row, row b at its OWN position pos[b] (models/
        scheduler.py). ids: [B, 1]; pos: [B] int32. Writes each row's
        K/V at its own cache column and attends per-row lengths; the
        shared cache.offset is NOT advanced — per-slot positions live
        with the scheduler. Returns (logits [B, V], cache)."""
        B, S = ids.shape
        assert S == 1, "slot decode feeds one token per slot"
        mlp_mode = mlp_mode or mode
        x = self.embed[ids].reshape(B, self.config.hidden_size)
        for li, layer in enumerate(self.layers):
            kv = cache.layer(li)
            h = rms_norm(x, layer.ln_attn, self.config.rms_norm_eps)
            a, kv = layer.attn.fwd_cached_slots(
                h, self.cos, self.sin, B, kv, pos, mode)
            cache = cache.set_layer(li, kv)
            x = x + a
            h = rms_norm(x, layer.ln_mlp, self.config.rms_norm_eps)
            x = x + layer.mlp(h, mlp_mode)
        x = rms_norm(x, self.final_norm, self.config.rms_norm_eps)
        if mode == "dist":
            x = self._gather_rows(x)
        return self._head(x), cache

    def forward_tokens_slots_verify(self, ids, cache: KVCache, pos,
                                    q_lens, mode: str = "dist",
                                    mlp_mode: Optional[str] = None):
        """Speculative-verify forward (models/spec_decode.py): each
        batch row is a slot scoring a variable-length draft window in
        ONE pass. ids: [B, S] — slot b's first q_lens[b] tokens occupy
        positions pos[b] .. pos[b] + q_lens[b] - 1 (padding past
        q_lens[b] is computed-and-discarded); K/V of the valid window
        rows are written at those cache columns (a rejected suffix is
        simply overwritten by the next step). Returns (per-position
        logits [B, S, V], cache)."""
        B, S = ids.shape
        mlp_mode = mlp_mode or mode
        x = self.embed[ids].reshape(B * S, self.config.hidden_size)
        for li, layer in enumerate(self.layers):
            kv = cache.layer(li)
            h = rms_norm(x, layer.ln_attn, self.config.rms_norm_eps)
            a, kv = layer.attn.fwd_cached_slots_verify(
                h, self.cos, self.sin, B, kv, pos, q_lens, mode)
            cache = cache.set_layer(li, kv)
            x = x + a
            h = rms_norm(x, layer.ln_mlp, self.config.rms_norm_eps)
            x = x + layer.mlp(h, mlp_mode)
        x = rms_norm(x, self.final_norm, self.config.rms_norm_eps)
        if mode == "dist":
            x = self._gather_rows(x)
        return self._head(x).reshape(B, S, -1), cache

    def forward_tokens_slots_paged_verify(self, ids, pcache, pos, q_lens,
                                          mode: str = "flash",
                                          mlp_mode: Optional[str] = None):
        """forward_tokens_slots_verify over the PAGED KV pool: the
        draft window's K/V resolves through the page table (padded rows
        scatter out of bounds and are dropped), and attention walks the
        pool with per-slot kv_lens AND q_lens. Returns (per-position
        logits [B, S, V], pcache)."""
        B, S = ids.shape
        mlp_mode = mlp_mode or mode
        x = self.embed[ids].reshape(B * S, self.config.hidden_size)
        for li, layer in enumerate(self.layers):
            h = rms_norm(x, layer.ln_attn, self.config.rms_norm_eps)
            if self.sp_axis is not None:
                a, kv = layer.attn.fwd_cached_slots_paged_verify_sp(
                    h, self.cos, self.sin, B, pcache.layer(li),
                    pcache.table, pos, q_lens, self.sp_axis, mode,
                    self.sp_combine)
            else:
                a, kv = layer.attn.fwd_cached_slots_paged_verify(
                    h, self.cos, self.sin, B, pcache.layer(li),
                    pcache.table, pos, q_lens, mode)
            pcache = pcache.set_layer(li, *kv)
            x = x + a
            h = rms_norm(x, layer.ln_mlp, self.config.rms_norm_eps)
            x = x + layer.mlp(h, mlp_mode)
        x = rms_norm(x, self.final_norm, self.config.rms_norm_eps)
        if mode == "dist":
            x = self._gather_rows(x)
        return self._head(x).reshape(B, S, -1), pcache

    def forward_tokens_slots_paged(self, ids, pcache, pos,
                                   mode: str = "flash",
                                   mlp_mode: Optional[str] = None):
        """Slot-masked decode forward over the PAGED KV pool
        (shared-prefix serving, models/prefix_cache.py): identical math
        to forward_tokens_slots, but each layer's KV lives in physical
        pages behind the shared page table — slot b attends whatever
        pages its table row maps, including pages shared read-only with
        other slots' cached prefixes. ids: [B, 1]; pos: [B] int32;
        pcache: PagedSlotCache. Returns (logits [B, V], pcache)."""
        B, S = ids.shape
        assert S == 1, "slot decode feeds one token per slot"
        mlp_mode = mlp_mode or mode
        x = self.embed[ids].reshape(B, self.config.hidden_size)
        for li, layer in enumerate(self.layers):
            h = rms_norm(x, layer.ln_attn, self.config.rms_norm_eps)
            if self.sp_axis is not None:
                # sequence-parallel paged decode: each chip walks its
                # own page shard, partials LSE-merge across sp
                a, kv = layer.attn.fwd_cached_slots_paged_sp(
                    h, self.cos, self.sin, B, pcache.layer(li),
                    pcache.table, pos, self.sp_axis, mode,
                    self.sp_combine)
            else:
                a, kv = layer.attn.fwd_cached_slots_paged(
                    h, self.cos, self.sin, B, pcache.layer(li),
                    pcache.table, pos, mode)
            pcache = pcache.set_layer(li, *kv)
            x = x + a
            h = rms_norm(x, layer.ln_mlp, self.config.rms_norm_eps)
            x = x + layer.mlp(h, mlp_mode)
        x = rms_norm(x, self.final_norm, self.config.rms_norm_eps)
        if mode == "dist":
            x = self._gather_rows(x)
        return self._head(x), pcache

    def forward_train(self, ids, mode: str = "train"):
        """Training forward (no KV cache): full-causal attention over
        each sequence, all-position logits [B, S, V].

        mode="train": every projection and the attention run through the
        framework's differentiable kernels (custom-VJP ag_gemm/gemm_rs +
        Pallas flash attention, kernels/grad.py + flash_attn_train.py) —
        the reference's autograd-wrapped dist path
        (layers/nvidia/tp_attn.py under torch.autograd).
        mode="xla": pure-XLA oracle for differential gradient tests.
        B*S must be divisible by the TP size for "train".
        """
        B, S = ids.shape
        impl = "flash" if mode == "train" else "ref"
        mlp_impl = "dist" if mode == "train" else "xla"
        x = self.embed[ids].reshape(B * S, self.config.hidden_size)
        for layer in self.layers:
            h = rms_norm(x, layer.ln_attn, self.config.rms_norm_eps)
            x = x + layer.attn.fwd_train(h, self.cos, self.sin, B, impl)
            h = rms_norm(x, layer.ln_mlp, self.config.rms_norm_eps)
            x = x + layer.mlp.fwd_train(h, mlp_impl)
        x = rms_norm(x, self.final_norm, self.config.rms_norm_eps)
        if mode == "train":
            # activations are row-sharded; gather for the LM head so the
            # head dot (and its transpose, d lm_head = x^T @ dlogits)
            # contracts a replicated dimension
            x = self._gather_rows(x)
        logits = jnp.dot(x, self.lm_head,
                         preferred_element_type=jnp.float32)
        return logits.reshape(B, S, -1)

    def _head(self, x):
        """Replicated rows [M, D] -> logits [M, V] f32, split over
        vocab_axis like the head: a local [M, D] x [D, V/n] product a
        chip, no collective. bf16 x bf16 -> f32 on the MXU; casting the
        [D, V] weight to f32 would materialize (and re-read) gigabytes
        per decode step. lm_head may be int8-quantized (the single
        biggest weight read of a decode step) — qmm dequants after the
        dot. The placement is PINNED, not left to propagation: the
        scheduler builds its carry to it (Engine.logits_sharding), and
        a tick that returned any other would compile the next again."""
        from triton_dist_tpu.kernels.quant import qmm
        logits = qmm(x, self.lm_head, preferred_element_type=jnp.float32)
        return split_last_axis(logits, self.mesh, self.vocab_axis)

    def _gather_rows(self, x):
        """Row-sharded [M, D] -> replicated (the LM-head prologue)."""
        import functools

        @functools.partial(
            jax.shard_map, mesh=self.mesh,
            in_specs=P(self.axis, None), out_specs=P(None, None),
            check_vma=False)
        def gather_rows(x_loc):
            return jax.lax.all_gather(x_loc, self.axis, axis=0,
                                      tiled=True)

        return gather_rows(x)

    def make_cache(self, batch: int, max_seq: int,
                   dtype=None) -> KVCache:
        cfg = self.config
        return KVCache.create(cfg.num_layers, batch, max_seq,
                              cfg.num_kv_heads, cfg.head_dim,
                              mesh=self.mesh, axis=self.axis,
                              dtype=dtype or cfg.jax_dtype)

    def serving_traits(self):
        return ServingTraits(kv_heads=self.config.num_kv_heads)

    def make_paged_cache(self, batch: int, max_seq: int, **kw):
        from triton_dist_tpu.models.kv_cache import uniform_paged_cache
        return uniform_paged_cache(self, batch, max_seq, **kw)
