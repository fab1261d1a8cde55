"""Trinity (`model_type: afmoe`, arcee-ai/Trinity-Mini) as ONE CHIP'S
SHARE of an expert-parallel deployment, served through the same Engine /
scheduler / TokenServer path as the other families.

    x = E[ids] * sqrt(D)
    x = x + RMSNorm_post_attn(Attn(RMSNorm_in(x)))
    x = x + RMSNorm_post_mlp(FFN(RMSNorm_pre_mlp(x)))
    logits = W_head RMSNorm_f(x)

A layer has TWO kinds (`AfmoeConfig.kind(li)`), neither following from
the other:

  attention  "swa"   `layer_types[li] == "sliding_attention"`: rotary
                     on q and k, the last `sliding_window` positions,
                     kept in a RING per slot
             "full"  every `global_attn_every_n_layers`-th layer: NO
                     rotary, every position, kept in PAGES
  FFN        "dense" li < `num_dense_layers`: SwiGLU (`TP_MLP`)
             "moe"   a shared SwiGLU expert computed whole (`TP_MLP`)
                     plus the routed part, an `EP_MoE` with a STATED
                     SHARE (`held = (first, count)` of `num_experts`):
                     sigmoid scores over every published expert, the
                     top-k of score + bias, weights from the unbiased
                     scores, normalised and scaled (`route_noaux_tc`
                     with one group), the chosen experts this chip
                     holds through the ragged grouped GEMM
                     (`EP_MoE.fwd_share`), what the other chips of the
                     layer would add LEFT OUT.

Attention is `layers/gated_attn.py` (QK-norm, a sigmoid gate on the
walk's output before W_o). The equations are written out in
benchmark/reference/afmoe.py, which the tier-1 tests hold this module
to, share for share.

STATE (kv_cache.HybridSlotCache with no state planes): a ring of
`sliding_window` rows per slot and window layer, position t in row
t % window, its key ROTATED at t before it is written; one fused K|V
pool per full layer behind the shared page table, keys unrotated. A
slot therefore holds state beside its pages (`ServingTraits.slot_state`):
it admits through `admit_slot_paged` below, and whatever would rebuild
a slot from pages alone is refused by name.

ADMISSION runs every layer over the whole prompt but the LAST: there
only the prompt's K and V are needed beside the last position's own
output, which is all the one logits row depends on.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from triton_dist_tpu.kernels.paged_kv import set_prompt_pages
from triton_dist_tpu.kernels.quant import qmm
from triton_dist_tpu.layers.common import precompute_rope, rms_norm
from triton_dist_tpu.layers.ep_moe import EP_MoE
from triton_dist_tpu.layers.gated_attn import GatedAttn
from triton_dist_tpu.layers.tp_mlp import TP_MLP
from triton_dist_tpu.models.utils import (EXPERTS_TOUCHED_COUNTERS,
                                          ServingTraits, place_replicated)
from triton_dist_tpu.runtime import auto_mesh


@dataclasses.dataclass(frozen=True)
class AfmoeConfig:
    hidden_size: int = 2048
    intermediate_size: int = 6144
    moe_intermediate_size: int = 1024
    num_layers: int = 32
    num_dense_layers: int = 2
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    sliding_window: int = 2048
    global_attn_every_n_layers: int = 4
    n_routed_experts: int = 128      # what the router ranks
    num_shared_experts: int = 1
    num_experts_per_tok: int = 8
    route_scale: float = 2.826
    # the share: experts held_first .. held_first + held_count - 1
    held_first: int = 0
    held_count: int = 128
    vocab_size: int = 200192
    max_position_embeddings: int = 131072
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    dtype: str = "bfloat16"
    model_type: str = "afmoe"
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
    def load_counters(self) -> tuple:
        """(name, help, labels) of the counters behind a tick's
        [.., dropped, pairs routed, pairs held], in `_zero_load`'s
        order."""
        return tuple(
            ("attn_kv_positions",
             "cached K/V positions the decode steps' attention read, over "
             "slots and layers, by the kind of layer: `window` from a "
             "ring (at most the window a slot and layer), `full` from "
             "pages (the whole context)", {"kind": kind})
            for kind in ("window", "full")) + EXPERTS_TOUCHED_COUNTERS

    def kind(self, li: int) -> Tuple[str, str]:
        """(attention kind, FFN kind) of layer li."""
        attn = ("full" if (li + 1) % self.global_attn_every_n_layers == 0
                else "swa")
        return attn, ("dense" if li < self.num_dense_layers else "moe")

    def kinds(self):
        return [self.kind(li) for li in range(self.num_layers)]


def tiny_afmoe(**overrides) -> AfmoeConfig:
    """Eight layers, two periods of (swa, swa, swa, full), the first
    dense; 4 query heads on 2 KV heads of 32; a window of 8; 16 routed
    experts top-4 of which share 1 of 4 holds four: the tier-1 tests'
    model."""
    base = dict(hidden_size=64, intermediate_size=128,
                moe_intermediate_size=32, num_layers=8, num_dense_layers=1,
                num_heads=4, num_kv_heads=2, head_dim=32, sliding_window=8,
                n_routed_experts=16, num_experts_per_tok=4, held_first=4,
                held_count=4, vocab_size=256, max_position_embeddings=256,
                dtype="float32")
    base.update(overrides)
    return AfmoeConfig(**base)


def _one_chip(mesh: Mesh, axis: str) -> Mesh:
    mesh = auto_mesh(mesh)
    if mesh.shape[axis] != 1:
        raise ValueError(
            f"Afmoe serves one chip's share (mesh axis {axis!r} has size "
            f"{mesh.shape[axis]}); missing capability: tensor-parallel "
            "gated attention over rings and the expert exchange across a "
            "mesh")
    return mesh


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class AfmoeLayer:
    attn: GatedAttn
    ln_in: jax.Array
    ln_post_attn: jax.Array
    ln_pre_mlp: jax.Array
    ln_post_mlp: jax.Array
    mlp: TP_MLP                        # the dense FFN, or the shared expert
    moe: Optional[EP_MoE]              # None in a dense layer


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class Afmoe:
    embed: jax.Array                   # [V, D]
    layers: Tuple[AfmoeLayer, ...]
    final_norm: jax.Array
    lm_head: jax.Array                 # [D, V], untied
    rope: jax.Array                    # the window layers' rotary table
    #                                    [T, d]: cos | sin of a position
    config: AfmoeConfig = dataclasses.field(metadata=dict(static=True))
    mesh: Mesh = dataclasses.field(metadata=dict(static=True))
    axis: str = dataclasses.field(metadata=dict(static=True))

    # -- construction --------------------------------------------------

    @staticmethod
    def make_layer(cfg: AfmoeConfig, li: int, w: dict, mesh: Mesh,
                   axis: str = "tp") -> AfmoeLayer:
        """One layer from a dict of plain arrays under the reference's
        names (benchmark/reference/afmoe.py `_layer_weights`); an
        expert layer's `we_*` hold the HELD experts only."""
        mesh = _one_chip(mesh, axis)
        a_kind, f_kind = cfg.kind(li)
        attn = GatedAttn.init(
            w["wq"], w["wk"], w["wv"], w["wg"], w["wo"], w["q_norm"],
            w["k_norm"], n_heads=cfg.num_heads,
            n_kv_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
            window=cfg.sliding_window if a_kind == "swa" else 0,
            eps=cfg.rms_norm_eps)
        moe = None
        if f_kind == "dense":
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
                noaux=(1, 1, cfg.route_scale))
        return AfmoeLayer(attn=attn, ln_in=w["ln_in"],
                          ln_post_attn=w["ln_post_attn"],
                          ln_pre_mlp=w["ln_pre_mlp"],
                          ln_post_mlp=w["ln_post_mlp"], mlp=mlp, moe=moe)

    @staticmethod
    def build(cfg: AfmoeConfig, head: dict, layers, mesh: Mesh,
              axis: str = "tp") -> "Afmoe":
        """head: {"embed", "final_norm", "lm_head"}; layers from
        `make_layer`. One chip: the mesh's `axis` must have size 1."""
        mesh = _one_chip(mesh, axis)
        if cfg.held_first < 0 or cfg.held_first + cfg.held_count \
                > cfg.n_routed_experts:
            raise ValueError(
                f"the share ({cfg.held_first}, {cfg.held_count}) lies "
                f"outside the {cfg.n_routed_experts} routed experts")
        cos, sin = precompute_rope(cfg.head_dim,
                                   cfg.max_position_embeddings,
                                   cfg.rope_theta)
        # one table, a position's cos and sin in one row of head_dim
        # lanes: a decode step gathers 64 rows of it (two tables of
        # head_dim / 2 lanes each were COPIED whole every step, 67 MB:
        # `copy f32[131072,64]`, PERF.md section 6, PR 43)
        model = Afmoe(
            embed=head["embed"], layers=tuple(layers),
            final_norm=head["final_norm"], lm_head=head["lm_head"],
            rope=jnp.concatenate([cos, sin], axis=-1), config=cfg,
            mesh=mesh, axis=axis)
        return place_replicated(model, mesh)

    @staticmethod
    def random_init(cfg: AfmoeConfig, mesh: Mesh, axis: str = "tp",
                    seed: int = 0) -> "Afmoe":
        """Random weights for tests and examples (the benchmark brings
        its own, from its reference)."""
        mesh = auto_mesh(mesh)
        D, I, F = (cfg.hidden_size, cfg.intermediate_size,
                   cfg.moe_intermediate_size)
        nq, nkv = cfg.num_heads * cfg.head_dim, \
            cfg.num_kv_heads * cfg.head_dim
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
            d = {"ln_in": one(D), "ln_post_attn": one(D),
                 "ln_pre_mlp": one(D), "ln_post_mlp": one(D),
                 "wq": w(D, nq), "wk": w(D, nkv), "wv": w(D, nkv),
                 "wg": w(D, nq), "wo": w(nq, D),
                 "q_norm": one(cfg.head_dim), "k_norm": one(cfg.head_dim)}
            if cfg.kind(li)[1] == "dense":
                d.update(w_gate=w(D, I), w_up=w(D, I), w_down=w(I, D))
            else:
                E, Eh = cfg.n_routed_experts, cfg.held_count
                Fs = cfg.num_shared_experts * F
                d.update(w_router=w(D, E),
                         e_bias=w(E, scale=0.02, dtype=jnp.float32),
                         ws_gate=w(D, Fs), ws_up=w(D, Fs),
                         ws_down=w(Fs, D), we_gate=w(Eh, D, F),
                         we_up=w(Eh, D, F), we_down=w(Eh, F, D))
            layers.append(Afmoe.make_layer(cfg, li, d, mesh, axis))
        head = {"embed": w(cfg.vocab_size, D, scale=0.25),
                "final_norm": one(D),
                "lm_head": w(D, cfg.vocab_size, scale=0.02)}
        return Afmoe.build(cfg, head, layers, mesh, axis)

    # -- what the Engine and the scheduler ask -------------------------

    def serving_traits(self) -> ServingTraits:
        return ServingTraits(kv_heads=self.config.num_kv_heads,
                             slot_state="window rings")

    def make_paged_cache(self, batch: int, max_seq: int, *, page: int,
                         num_pages: int, dtype=None,
                         sp_axis: Optional[str] = None):
        from triton_dist_tpu.models.kv_cache import HybridSlotCache
        cfg = self.config
        attn = [a for a, _ in cfg.kinds()]
        return HybridSlotCache.create_hybrid(
            batch, max_seq, heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
            window=cfg.sliding_window, window_layers=attn.count("swa"),
            state_layers=0,
            attn_layers=cfg.num_layers, page=page, num_pages=num_pages,
            mesh=self.mesh, axis=self.axis, dtype=dtype or cfg.jax_dtype,
            paged_layers=attn.count("full"), fused=True)

    def _zero_load(self):
        """Fresh routing-load accumulator of a tick: [expert_tokens of
        the held experts, dropped, pairs routed, pairs held, K/V
        positions read from rings, from pages, held experts with a
        pair, held experts]."""
        return jnp.zeros((self.config.held_count + 7,), jnp.int32)

    # -- pieces both forwards share ------------------------------------

    def _embed(self, ids):
        """`mup_enabled`: the embedding's output times sqrt(D)."""
        x = self.embed[ids]
        return (x.astype(jnp.float32)
                * math.sqrt(self.config.hidden_size)).astype(x.dtype)

    @staticmethod
    def _rope_rows(rows):
        """Rows of the table -> (cos, sin) [M, d / 2]."""
        return tuple(jnp.split(rows, 2, axis=-1))

    def _ffn(self, layer: AfmoeLayer, x, mode: str, load):
        """x + RMSNorm_post_mlp(FFN(RMSNorm_pre_mlp(x))); `load`
        accumulates the expert layer's routing stats when the caller
        asked for them."""
        eps = self.config.rms_norm_eps
        m = rms_norm(x, layer.ln_pre_mlp, eps)
        mlp_mode = "xla" if mode == "xla" else "flash"
        if layer.moe is None:
            with jax.named_scope("dense_mlp"):
                y = layer.mlp(m, mlp_mode)
        else:
            with jax.named_scope("shared_expert"):
                y = layer.mlp(m, mlp_mode)
            r, st = layer.moe.fwd_share(m, return_stats=True)
            y = y + r.astype(y.dtype)
            if load is not None:
                counts = st["expert_tokens"]
                load = load + jnp.concatenate([counts, jnp.stack([
                    st["dropped"], st["pairs_routed"], st["pairs_held"],
                    0, 0, jnp.sum(counts > 0), counts.shape[0]])]
                ).astype(jnp.int32)
        return x + rms_norm(y, layer.ln_post_mlp, eps), load

    def _logits(self, x):
        x = rms_norm(x, self.final_norm, self.config.rms_norm_eps)
        return qmm(x, self.lm_head, preferred_element_type=jnp.float32)

    # -- decode: one token for every slot ------------------------------

    def forward_tokens_slots_paged(self, ids, pcache, pos,
                                   mode: str = "flash",
                                   return_moe_stats: bool = False):
        """Slot-masked decode over rings and pages: ids [B, 1], pos [B]
        (each slot's own position). Returns (logits [B, V], pcache[,
        the tick's routing-load vector])."""
        cfg = self.config
        impl = "ref" if mode == "xla" else "flash"
        pos = jnp.asarray(pos, jnp.int32)
        load = self._zero_load() if return_moe_stats else None
        x = self._embed(ids[:, 0])
        rope = self._rope_rows(self.rope[pos])     # once, for every layer
        win_k, win_v = list(pcache.win_k), list(pcache.win_v)
        pools = list(pcache.pages_k)
        i_win = i_full = 0
        for layer in self.layers:
            u = rms_norm(x, layer.ln_in, cfg.rms_norm_eps)
            if layer.attn.window:
                a, win_k[i_win], win_v[i_win] = layer.attn.decode_ring(
                    u, rope, win_k[i_win], win_v[i_win], pos, impl=impl)
                i_win += 1
            else:
                a, pools[i_full] = layer.attn.decode_paged(
                    u, pools[i_full], pcache.table, pos, impl=impl)
                i_full += 1
            x = x + rms_norm(a, layer.ln_post_attn, cfg.rms_norm_eps)
            x, load = self._ffn(layer, x, mode, load)
        pcache = dataclasses.replace(
            pcache, pages_k=tuple(pools), win_k=tuple(win_k),
            win_v=tuple(win_v))
        if return_moe_stats:
            n = cfg.held_count
            lens = pos + 1
            load = load.at[n + 3:n + 5].add(jnp.stack([
                i_win * jnp.sum(jnp.minimum(lens, cfg.sliding_window)),
                i_full * jnp.sum(lens)]).astype(jnp.int32))
            return self._logits(x), pcache, load
        return self._logits(x), pcache

    # -- admission: a whole prompt into one slot -----------------------

    def admit_slot_paged(self, ids, pcache, rows, slot, n,
                         mode: str = "flash"):
        """ids [1, P]: the prompt, zero-padded to its bucket; n: its
        real length; rows [maxp]: the slot's table row. Installs the
        row, leaves the slot's rings holding the prompt's last `window`
        rotated rows and its pages the prompt's unrotated ones, and
        returns (logits [1, V] of its last token, pcache)."""
        cfg = self.config
        impl = "ref" if mode == "xla" else "flash"
        P_ = ids.shape[1]
        W, page = cfg.sliding_window, pcache.page
        kd = pcache.pages_k[0].dtype
        last = n - 1
        row = lambda a: jax.lax.dynamic_slice_in_dim(a, last, 1, 0)  # noqa
        # ring row r ends up holding the last prompt position congruent
        # to it (rows no position reached keep what they had: the
        # slot's lengths mask them until decode overwrites them)
        r = jnp.arange(W)
        src = jnp.minimum(r + (jnp.maximum(last - r, 0) // W) * W, P_ - 1)
        ring_ok = (r <= last)[None, :, None]

        def to_ring(ring, new):                  # new [P, Hkv, d]
            picked = jnp.swapaxes(new[src], 0, 1).astype(ring.dtype)
            cur = jax.lax.dynamic_slice_in_dim(ring, slot, 1, 0)[0]
            return jax.lax.dynamic_update_slice_in_dim(
                ring, jnp.where(ring_ok, picked, cur)[None], slot, 0)

        # the prompt's pages; the trash page for one wholly past its end
        npg = -(-P_ // page)
        pids = jnp.where(jnp.arange(npg) * page < n, rows[:npg],
                         pcache.trash)

        x = self._embed(ids[0])                  # [P, D], then [1, D]
        rope = self._rope_rows(self.rope[:P_])
        win_k, win_v = list(pcache.win_k), list(pcache.win_v)
        pools = list(pcache.pages_k)
        i_win = i_full = 0
        for li, layer in enumerate(self.layers):
            u = rms_norm(x, layer.ln_in, cfg.rms_norm_eps)
            if li == len(self.layers) - 1 and not layer.attn.window:
                # the last layer: the prompt's K and V, and the last
                # position's output alone
                x = row(x)
                a, k, v = layer.attn.last_query(u, row(u), n, dtype=kd)
            else:
                a, k, v = layer.attn.prefill(u, rope, impl=impl, dtype=kd)
            if layer.attn.window:
                win_k[i_win] = to_ring(win_k[i_win], k)
                win_v[i_win] = to_ring(win_v[i_win], v)
                i_win += 1
            else:
                pools[i_full] = set_prompt_pages(
                    pools[i_full], pids, jnp.concatenate([k, v], axis=1))
                i_full += 1
            x = x + rms_norm(a, layer.ln_post_attn, cfg.rms_norm_eps)
            x, _ = self._ffn(layer, x, mode, None)
        table = jax.lax.dynamic_update_slice(pcache.table, rows[None],
                                             (slot, 0))
        pcache = dataclasses.replace(
            pcache, pages_k=tuple(pools), win_k=tuple(win_k),
            win_v=tuple(win_v), table=table,
            live=pcache.live.at[slot].set(True))
        if x.shape[0] != 1:
            x = row(x)
        return self._logits(x), pcache
