"""Plain reference for DeepSeek-V3 (`model_type: deepseek_v3`) as ONE
CHIP'S SHARE of an expert-parallel deployment, and the benchmark's
weights for it.

Straightforward `jax.numpy`, float32, matmuls at `highest` precision:
attention in its EXPANDED form over full [S, S] masks, every held expert
computed densely over every token and weighted by its (mostly zero)
routing weight; no cache, no kernel, no grouping. It imports nothing of
the program (the matmul, its control's rounding and the seeded normal
are `reference/qwen3.py`'s).

Equations (config.json of deepseek-ai/DeepSeek-V3 and its published
modeling code), x a layer's input, H heads, per head nope 128 | rope 64,
v 128:
    x = x + MLA(RMSNorm(x));  x = x + FFN(RMSNorm(x))
    FFN = SwiGLU(intermediate_size) in the first `first_k_dense_replace`
    layers, Shared(u) + Routed(u) after them; final RMSNorm, untied head.
  MLA   c_q = RMSNorm(u W_qa); q = c_q W_qb -> [H, 128 | 64];
        a = u W_kva [kv_lora_rank + 64]; c_kv = RMSNorm(a[:rank]);
        k_pe = RoPE(a[rank:]) (one for all heads); [k_nope | v] =
        c_kv W_kvb -> [H, 128 | 128]; q_pe = RoPE(q_pe); per head
        softmax(([q_nope | q_pe] . [k_nope | k_pe]) s) v over the causal
        prefix; heads concatenated through W_o. No biases.
  RoPE  YaRN over the 64 rope dims (`rope_scaling`): dim(r) = 64 ln(orig
        / (2 pi r)) / (2 ln theta), low = floor(dim(beta_fast)), high =
        ceil(dim(beta_slow)), ramp = clip((i - low) / (high - low), 0, 1),
        inv_freq = theta^(-2i/64) / factor * ramp + theta^(-2i/64) (1 -
        ramp); cos/sin scaled by m(factor, mscale) / m(factor,
        mscale_all_dim) (1 as published); s = 192^-0.5 m(factor,
        mscale_all_dim)^2, m(f, a) = 0.1 a ln f + 1.
  Router (`noaux_tc`, float32 in every precision, as published): sc =
        sigmoid(u W_r); sb = sc + e_score_correction_bias; a group's
        score = the sum of its two largest sb; the `topk_group` best of
        `n_group` groups are kept, the others' sb set to 0.0; top-k of
        that; w = sc[chosen] / (sum + 1e-20) * routed_scaling_factor.
        Routed(u) = sum_i w_i Expert_i(u); experts and Shared are SwiGLU
        of `moe_intermediate_size`.
  Share the chip holds experts first .. first + held - 1 (`held` =
        the file's `n_routed_experts`, `first` = `deployment.ep_rank` x
        held) of `deployment.routed_experts_total`. It routes over all
        of them and adds w_i Expert_i(u) for the chosen experts it holds
        only; what the other chips would add is LEFT OUT, and that
        partial sum goes on to the next layer. Shared(u) is whole.

Departures from the published checkpoint and code: the weights are
random (below). RoPE pairs dim i with dim i + 32 (the half-split
"rotate_half" layout, which the published code reaches by permuting the
interleaved (2i, 2i+1) columns first: with random W_qb / W_kva columns
the permutation is immaterial); the program stores k_pe roped in the
same pairing. `num_nextn_predict_layers` is 0: the multi-token
prediction module is not built (the published report discards it at
inference). The share above is the configuration's cut, not the
model's. Nothing else is known to differ.

Weights: matrices normal with std fan_in**-0.5 in the configuration's
dtype, embedding and head 0.02, RMSNorm weights 1 + 0.1 normal,
`e_score_correction_bias` 0.02 normal float32 (so that the biased
selection differs from the unbiased one now and then). Expert e's
matrices are made from a key folded with its GLOBAL number, so every
share of a layer holds the same experts the uncut layer has.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.qwen3 import (HIGHEST, _key, _mm as _mm_control,
                                       _normal, _rms)


def _bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _mm(x, w, precision):
    """`qwen3._mm` (float32 at `highest`; the int8 / fp8 controls round
    both inputs first), and one precision more for reading a limit:
    "bf16", the precision the configuration STATES, both inputs rounded
    to bfloat16 (and `_layer` rounds the residual stream): what this
    reference itself reads when computed as the program computes, with
    no kernel, cache or share of the program's in it."""
    if precision == "bf16":
        return jnp.matmul(_bf16(x), _bf16(w), precision=HIGHEST)
    return _mm_control(x, w, precision)


def sizes(cfg: dict) -> dict:
    dep = cfg["deployment"]
    held = int(cfg["n_routed_experts"])
    return dict(
        D=cfg["hidden_size"], I=cfg["intermediate_size"],
        F=cfg["moe_intermediate_size"], L=cfg["num_hidden_layers"],
        dense=int(cfg["first_k_dense_replace"]),
        H=cfg["num_attention_heads"], Rq=cfg["q_lora_rank"],
        Rkv=cfg["kv_lora_rank"], nope=cfg["qk_nope_head_dim"],
        rope=cfg["qk_rope_head_dim"], vd=cfg["v_head_dim"],
        E=int(dep["routed_experts_total"]), held=held,
        first=int(dep["ep_rank"]) * held,
        shared=int(cfg["n_shared_experts"]),
        k=int(cfg["num_experts_per_tok"]), groups=int(cfg["n_group"]),
        topk_group=int(cfg["topk_group"]),
        route_scale=float(cfg["routed_scaling_factor"]),
        V=cfg["vocab_size"], theta=float(cfg["rope_theta"]),
        eps=float(cfg["rms_norm_eps"]),
        dtype=jnp.dtype({"bfloat16": jnp.bfloat16,
                         "float32": jnp.float32}[cfg["torch_dtype"]]))


def layer_kind(cfg: dict, li: int) -> str:
    return "dense" if li < cfg["first_k_dense_replace"] else "moe"


# ----------------------------------------------------------------------
# YaRN tables and the softmax scale
# ----------------------------------------------------------------------

def _mscale(factor: float, a: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * a * math.log(factor) + 1.0


def softmax_scale(cfg: dict) -> float:
    rs = cfg["rope_scaling"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return qk ** -0.5 * _mscale(rs["factor"], rs["mscale_all_dim"]) ** 2


def yarn_inv_freq(cfg: dict) -> np.ndarray:
    rs, dim = cfg["rope_scaling"], cfg["qk_rope_head_dim"]
    theta, orig = float(cfg["rope_theta"]), \
        rs["original_max_position_embeddings"]
    corr = lambda r: dim * math.log(orig / (2 * math.pi * r)) / (  # noqa
        2 * math.log(theta))
    low = max(math.floor(corr(rs["beta_fast"])), 0)
    high = min(math.ceil(corr(rs["beta_slow"])), dim - 1)
    i = np.arange(dim // 2, dtype=np.float64)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    base = theta ** (-2.0 * i / dim)
    return base / rs["factor"] * ramp + base * (1.0 - ramp)


def rope_tables(cfg: dict, n: int):
    """(cos, sin) [n, rope / 2] float32, the cos/sin scale folded in."""
    rs = cfg["rope_scaling"]
    m = _mscale(rs["factor"], rs["mscale"]) / _mscale(
        rs["factor"], rs["mscale_all_dim"])
    f = np.outer(np.arange(n, dtype=np.float64), yarn_inv_freq(cfg))
    return (jnp.asarray(np.cos(f) * m, jnp.float32),
            jnp.asarray(np.sin(f) * m, jnp.float32))


def _rope(x, cos, sin):
    """x [..., S, h, rope] or [..., S, rope]; cos/sin [S, rope / 2];
    dim i pairs with dim i + rope / 2."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    if x.ndim == cos.ndim + 2:
        cos, sin = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


# ----------------------------------------------------------------------
# weights
# ----------------------------------------------------------------------

def _near_one(key, n, dtype):
    return (1.0 + 0.1 * jax.random.normal(key, (n,), jnp.float32)
            ).astype(dtype)


def _expert(key, *, D, F, dtype):
    ks = jax.random.split(key, 3)
    return (_normal(ks[0], (D, F), D ** -0.5, dtype),
            _normal(ks[1], (D, F), D ** -0.5, dtype),
            _normal(ks[2], (F, D), F ** -0.5, dtype))


def _layer_weights(key, *, kind, D, I, F, H, Rq, Rkv, nope, rope, vd, E,
                   held, first, shared, dtype):
    ks = iter(jax.random.split(key, 24))
    w = {"ln_attn": _near_one(next(ks), D, dtype),
         "ln_mlp": _near_one(next(ks), D, dtype),
         "w_qa": _normal(next(ks), (D, Rq), D ** -0.5, dtype),
         "q_norm": _near_one(next(ks), Rq, dtype),
         "w_qb": _normal(next(ks), (Rq, H * (nope + rope)), Rq ** -0.5,
                         dtype),
         "w_kva": _normal(next(ks), (D, Rkv + rope), D ** -0.5, dtype),
         "kv_norm": _near_one(next(ks), Rkv, dtype),
         "w_kvb": _normal(next(ks), (Rkv, H * (nope + vd)), Rkv ** -0.5,
                          dtype),
         "w_o": _normal(next(ks), (H * vd, D), (H * vd) ** -0.5, dtype)}
    if kind == "dense":
        g, u, d = _expert(next(ks), D=D, F=I, dtype=dtype)
        w.update(w_gate=g, w_up=u, w_down=d)
        return w
    w["w_router"] = _normal(next(ks), (D, E), D ** -0.5, dtype)
    w["e_bias"] = _normal(next(ks), (E,), 0.02, jnp.float32)
    g, u, d = _expert(next(ks), D=D, F=shared * F, dtype=dtype)
    w.update(ws_gate=g, ws_up=u, ws_down=d)
    ek = next(ks)
    eks = jax.vmap(lambda e: jax.random.fold_in(ek, e))(
        first + jnp.arange(held))
    g, u, d = jax.vmap(functools.partial(_expert, D=D, F=F, dtype=dtype))(
        eks)
    w.update(we_gate=g, we_up=u, we_down=d)      # [held, D, F] x 2, [held, F, D]
    return w


_WEIGHT_KEYS = ("D", "I", "F", "H", "Rq", "Rkv", "nope", "rope", "vd", "E",
                "held", "first", "shared", "dtype")


def layer_weights_fn(cfg: dict, kind: str, out_sharding=None):
    """A jitted `key -> layer dict` for one kind of layer ("dense" or
    "moe") of this configuration and this share."""
    s = sizes(cfg)
    fn = functools.partial(_layer_weights, kind=kind,
                           **{k: s[k] for k in _WEIGHT_KEYS})
    return jax.jit(fn, out_shardings=out_sharding)


def layer_key(seed: int, li: int):
    return jax.random.fold_in(_key(seed), li)


def head_key(seed: int):
    return jax.random.fold_in(_key(seed), 1 << 20)


def head_weights(cfg: dict, seed: int, out_sharding=None) -> dict:
    """{"embed" [V, D], "final_norm" [D], "lm_head" [D, V]}."""
    s = sizes(cfg)
    ks = jax.random.split(head_key(seed), 3)
    jit = lambda f: jax.jit(f, out_shardings=out_sharding)  # noqa: E731
    return {
        "embed": jax.block_until_ready(jit(lambda k: _normal(
            k, (s["V"], s["D"]), 0.02, s["dtype"]))(ks[0])),
        "final_norm": jit(lambda k: _near_one(k, s["D"], s["dtype"]))(
            ks[1]),
        "lm_head": jax.block_until_ready(jit(lambda k: _normal(
            k, (s["D"], s["V"]), 0.02, s["dtype"]))(ks[2]))}


# ----------------------------------------------------------------------
# the forward pass
# ----------------------------------------------------------------------

def route(u, w_router, e_bias, *, k, groups, topk_group, route_scale):
    """`noaux_tc` over u [..., D] float32: (weights [..., k], expert
    numbers [..., k]) over ALL the experts the router has columns for.
    Selection reads the biased scores, the weights the unbiased ones."""
    sc = jax.nn.sigmoid(jnp.matmul(u, w_router, precision=HIGHEST))
    sb = sc + e_bias
    E = sb.shape[-1]
    grouped = sb.reshape(sb.shape[:-1] + (groups, E // groups))
    gscore = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
    _, gidx = jax.lax.top_k(gscore, topk_group)
    gmask = jnp.any(jnp.arange(groups) == gidx[..., None], axis=-2)
    masked = jnp.where(gmask[..., None], grouped, 0.0).reshape(sb.shape)
    _, idx = jax.lax.top_k(masked, k)
    w = jnp.take_along_axis(sc, idx, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * route_scale
    return w, idx.astype(jnp.int32)


def _swiglu(u, g, up, d, precision):
    return _mm(jax.nn.silu(_mm(u, g, precision)) * _mm(u, up, precision),
               d, precision)


def routed_share(u, w, s, precision, with_held: bool = False):
    """What the held experts add for u [B, S, D]: every held expert over
    every token, times its routing weight (zero where it was not
    chosen). with_held: also which held experts each token chose
    [B, S, held] bool."""
    wts, idx = route(u, w["w_router"], w["e_bias"], k=s["k"],
                     groups=s["groups"], topk_group=s["topk_group"],
                     route_scale=s["route_scale"])

    def one(acc, ew):
        e, g, up, d = ew
        gate = jnp.sum(jnp.where(idx == e, wts, 0.0), axis=-1)
        return acc + gate[..., None] * _swiglu(u, g, up, d, precision), None

    held_ids = s["first"] + jnp.arange(s["held"])
    acc, _ = jax.lax.scan(
        one, jnp.zeros_like(u),
        (held_ids, w["we_gate"], w["we_up"], w["we_down"]))
    if with_held:
        return acc, jnp.any(idx[..., None] == held_ids, axis=-2)
    return acc


def _mla(u, w, cos, sin, s, scale, precision, head_block=8):
    B, S, _ = u.shape
    H, nope, rope, vd, Rkv = s["H"], s["nope"], s["rope"], s["vd"], s["Rkv"]
    c_q = _rms(_mm(u, w["w_qa"], precision), w["q_norm"], s["eps"])
    q = _mm(c_q, w["w_qb"], precision).reshape(B, S, H, nope + rope)
    a = _mm(u, w["w_kva"], precision)
    c_kv = _rms(a[..., :Rkv], w["kv_norm"], s["eps"])
    k_pe = _rope(a[..., Rkv:], cos, sin)                    # [B, S, rope]
    kv = _mm(c_kv, w["w_kvb"], precision).reshape(B, S, H, nope + vd)
    q_pe = _rope(q[..., nope:], cos, sin)
    q = jnp.concatenate([q[..., :nope], q_pe], -1)
    k = jnp.concatenate(
        [kv[..., :nope],
         jnp.broadcast_to(k_pe[:, :, None], (B, S, H, rope))], -1)
    v = kv[..., nope:]
    causal = jnp.tril(jnp.ones((S, S), bool))

    def heads(qkv):                  # [hb, B, S, .] a block of heads
        qh, kh, vh = qkv
        sc = jnp.einsum("hbqd,hbkd->hbqk", qh, kh, precision=HIGHEST)
        p = jax.nn.softmax(jnp.where(causal, sc * scale, -jnp.inf), -1)
        return jnp.einsum("hbqk,hbkd->hbqd", p, vh, precision=HIGHEST)

    hb = math.gcd(H, head_block)
    lead = lambda t: jnp.moveaxis(t, 2, 0).reshape(  # noqa: E731
        (H // hb, hb, B, S, t.shape[-1]))
    o = jax.lax.map(heads, (lead(q), lead(k), lead(v)))
    o = jnp.moveaxis(o.reshape(H, B, S, vd), 0, 2).reshape(B, S, H * vd)
    return _mm(o, w["w_o"], precision)


def _layer(x, w, cos, sin, *, kind, skey, scale, precision):
    """(the layer's output, which held experts each position chose
    [B, S, held] bool: all False in a dense layer)."""
    s = dict(skey)
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    x = x + _mla(_rms(x, w["ln_attn"], s["eps"]), w, cos, sin, s, scale,
                 precision)
    stream = _bf16 if precision == "bf16" else (lambda a: a)
    x = stream(x)
    u = _rms(x, w["ln_mlp"], s["eps"])
    if kind == "dense":
        return stream(x + _swiglu(u, w["w_gate"], w["w_up"], w["w_down"],
                                  precision)), \
            jnp.zeros(x.shape[:2] + (s["held"],), bool)
    routed, held = routed_share(u, w, s, precision, with_held=True)
    return stream(x + _swiglu(u, w["ws_gate"], w["ws_up"], w["ws_down"],
                              precision) + routed), held


@functools.lru_cache(maxsize=None)
def _layer_fn(kind, precision, skey, scale):
    return jax.jit(functools.partial(_layer, kind=kind, skey=skey,
                                     scale=scale, precision=precision))


def _skey(s):
    return tuple(sorted((k, v) for k, v in s.items() if k != "dtype"))


def layer_forward(cfg: dict, li: int, x, w, cos, sin, precision="f32",
                  with_held: bool = False):
    """One layer of this share over x [B, S, D] float32 (with_held: and
    the held experts each position chose)."""
    out = _layer_fn(layer_kind(cfg, li), precision, _skey(sizes(cfg)),
                    softmax_scale(cfg))(x, w, cos, sin)
    return out if with_held else out[0]


def _weights_fns(cfg):
    return {k: layer_weights_fn(cfg, k) for k in ("dense", "moe")}


def all_logits(cfg: dict, seed: int, ids, precision: str = "f32"):
    """float32 logits [S, V] of every position of one short sequence."""
    s = sizes(cfg)
    ids = np.asarray(ids, np.int32)[None]
    cos, sin = rope_tables(cfg, ids.shape[1])
    hw = head_weights(cfg, seed)
    x = hw["embed"][ids].astype(jnp.float32)
    fns = _weights_fns(cfg)
    for li in range(s["L"]):
        w = fns[layer_kind(cfg, li)](layer_key(seed, li))
        x = layer_forward(cfg, li, x, w, cos, sin, precision)
    h = _rms(x[0], hw["final_norm"].astype(jnp.float32), s["eps"])
    return _mm(h, hw["lm_head"].astype(jnp.float32), precision)


def _logit_rows(hidden, head, rows, precision):
    return _mm(hidden[rows], head, precision)


def served_token_gaps(cfg: dict, seed: int, sequences, prompt_lens, *,
                      precisions=("f32",), device=None, block_rows=256,
                      seq_block=1, pad_to=128):
    """`compare.py`'s contract, as `qwen3.served_token_gaps` states it:
    for every served token, how far its logit lies below the
    reference's best at that position; for a control precision, the
    same gap of the token that precision's own pass puts first. A
    layer's weights are made (3.75 GB in float32 for an expert layer of
    the published widths), used on every sequence and dropped; one
    sequence at a time keeps a 4,096-token pass's projections near 1 GB."""
    s = sizes(cfg)
    device = device or jax.devices()[0]
    n_seq = len(sequences)
    S = -(-max(len(q) for q in sequences) // pad_to) * pad_to
    ids = np.zeros((n_seq, S), np.int32)
    for i, q in enumerate(sequences):
        ids[i, :len(q)] = np.asarray(q, np.int32)
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    with jax.default_device(device):
        cos, sin = rope_tables(cfg, S)
        hw = head_weights(cfg, seed)
        embed = hw["embed"]
        fns = _weights_fns(cfg)
        blocks = range(0, n_seq, seq_block)
        hidden = {p: [f32(embed[ids[b:b + seq_block]]) for b in blocks]
                  for p in precisions}
        del embed
        # positions at which a lower precision's pass chose another SET
        # of held experts than the float32 pass, in any layer
        flipped = {p: [np.zeros(x.shape[:2], bool) for x in hidden[p]]
                   for p in precisions if p != "f32"}
        for li in range(s["L"]):
            w = fns[layer_kind(cfg, li)](layer_key(seed, li))
            held = {}
            for p in precisions:
                outs = [layer_forward(cfg, li, x, w, cos, sin, p,
                                      with_held=True) for x in hidden[p]]
                hidden[p] = [o[0] for o in outs]
                held[p] = [np.asarray(o[1]) for o in outs]
            for p, marks in flipped.items():
                for j, mark in enumerate(marks):
                    mark |= np.any(held[p][j] != held["f32"][j], axis=-1)
            del w, held
        final_w, lm = f32(hw["final_norm"]), f32(hw["lm_head"])
        del hw
        out = {p: [] for p in precisions}
        rows_fn = jax.jit(_logit_rows, static_argnames=("precision",))
        for i, (q, n0) in enumerate(zip(sequences, prompt_lens)):
            pos = np.arange(n0 - 1, len(q) - 1)
            served = np.asarray(q[n0:], np.int32)
            gaps = {p: [] for p in precisions}
            b, j = divmod(i, seq_block)
            h = {p: _rms(hidden[p][b][j], final_w, s["eps"])
                 for p in precisions}
            for r0 in range(0, len(pos), block_rows):
                rows = np.zeros((block_rows,), np.int32)
                chunk = pos[r0:r0 + block_rows]
                rows[:len(chunk)] = chunk
                ref = rows_fn(h["f32"], lm, rows, precision="f32")
                best = ref.max(axis=-1)
                tok = np.zeros((block_rows,), np.int32)
                tok[:len(chunk)] = served[r0:r0 + block_rows]
                for p in precisions:
                    pick = jnp.asarray(tok) if p == "f32" else jnp.argmax(
                        rows_fn(h[p], lm, rows, precision=p), axis=-1)
                    g = best - jnp.take_along_axis(
                        ref, pick[:, None], axis=-1)[:, 0]
                    gaps[p].append(np.asarray(g)[:len(chunk)])
            for p in precisions:
                out[p].append(np.concatenate(gaps[p]) if gaps[p]
                              else np.zeros((0,), np.float32))
        for p, marks in flipped.items():
            at = [marks[i // seq_block][i % seq_block][n0 - 1:len(q) - 1]
                  for i, (q, n0) in enumerate(zip(sequences, prompt_lens))]
            at = np.concatenate(at) if at else np.zeros((0,), bool)
            if at.size:
                print(f"held-pair set differs from float32's ({p} pass of "
                      f"the reference, any layer) at {int(at.sum())} of "
                      f"{at.size} served positions = "
                      f"{100.0 * at.mean():.2f} %", flush=True)
        # for reading a run by hand: does the gap grow along a stream
        # (a cache or position fault) or not (rounding, a routing flip)
        for p in precisions:
            fifths = [np.array_split(g, 5) for g in out[p] if g.size >= 5]
            if fifths:
                print(f"gap by fifth of the served stream ({p}): " + " ".join(
                    f"{np.mean(np.concatenate([f[i] for f in fifths])):.5f}"
                    for i in range(5)), flush=True)
    return out
