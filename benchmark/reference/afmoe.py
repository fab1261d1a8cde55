"""Plain reference for Trinity-Mini (`model_type: afmoe`) as ONE CHIP'S
SHARE of an expert-parallel deployment, and the benchmark's weights for
it.

Straightforward `jax.numpy`, float32, matmuls at `highest` precision:
attention over full [rows, S] masks in blocks of query rows so that
~12k positions fit, every held expert computed densely over every token
and weighted by its (mostly zero) routing weight; no cache, no ring, no
kernel, no grouping, one sequence at a time. It imports nothing of the
program (the matmul, its controls' rounding and the seeded normal are
`reference/qwen3.py`'s).

Equations (config.json of arcee-ai/Trinity-Mini as the catalog carries
it; what its keys do not say is the published `modeling_afmoe.py`'s and
is listed under ASSUMED), x a layer's input, D `hidden_size`, eps 1e-5:
    x = E[ids] sqrt(D)                    (`mup_enabled`)
    x = x + RMSNorm_post_attn(Attn(RMSNorm_in(x)))
    x = x + RMSNorm_post_mlp(FFN(RMSNorm_pre_mlp(x)))
    logits = W_head RMSNorm_f(x)          (untied head, no biases)
  Attn  a the normed input; q = a W_q -> [32, 128]; k = a W_k, v = a W_v
        -> [4, 128]; g = a W_g -> [4096]; q, k <- RMSNorm_128 per head
        (one learned weight each). `layer_types[l]`:
        "sliding_attention": rotary on all 128 dims of q and k at
        position t (`rope_theta` 10,000, `rope_scaling` null, dim i
        paired with dim i + 64); t attends s with t - W < s <= t, W =
        `sliding_window` (itself and the W - 1 before).
        "full_attention" (every `global_attn_every_n_layers`-th): NO
        rotary; t attends every s <= t.
        o = softmax(q k^T 128^-0.5) v; o <- o * sigmoid(g); Attn = o W_o.
  FFN   layers l < `num_dense_layers`: SwiGLU at `intermediate_size`.
        Others: Shared(m) + Routed(m). Shared: SwiGLU at
        `num_shared_experts` x `moe_intermediate_size`. Router, float32
        in every precision: s = sigmoid(m W_r) over all experts
        (`score_func`); the `num_experts_per_tok` largest of s + b are
        chosen (b = `expert_bias`, float32; `n_group` = `topk_group` =
        1: no group step); w = s[chosen] / (sum + 1e-20) x `route_scale`
        (`route_norm`); Routed(m) = sum_i w_i SwiGLU_i(m) at
        `moe_intermediate_size`.
  Share the chip holds experts first .. first + held - 1 (`held` = the
        file's `num_experts`, `first` = `deployment.ep_rank` x held) of
        `deployment.routed_experts_total`. It routes over all of them
        and adds w_i Expert_i(m) for the chosen experts it holds only;
        what the other chips would add is LEFT OUT, and that partial
        sum goes on to the next layer. Shared(m) is whole.

ASSUMED (the catalog's `config` does not carry them; each is in the
configuration file's `assumed`): QK-norm; no rotary on the full layers;
the gate projection W_g with its sigmoid between attention and W_o; the
four norms a layer with the residual taking the NORMED block output;
bfloat16. Departures from the published checkpoint: the weights are
random (below); the share above is the configuration's cut, not the
model's. Nothing else is known to differ.

Controls (`precision`): "int8" / "fp8" round both inputs of every
matmul (`qwen3._mm`); "bf16", the precision the configuration states,
rounds them to bfloat16 and rounds the residual stream and what the
program would cache (q, and k AFTER its rotary, v) too. Scores, softmax,
the gate's product, the norms and the router stay float32 in every
precision.

Weights: matrices normal with std fan_in**-0.5 in the configuration's
dtype, head 0.02, RMSNorm weights 1 + 0.1 normal, `expert_bias` 0.02
normal float32 (so that the biased selection differs from the unbiased
one now and then), EMBEDDING std 0.25. Expert e's matrices are made
from a key folded with its GLOBAL number, so every share of a layer
holds the same experts the uncut layer has.

Why the embedding is 0.25: every block's output passes a norm before
it joins the residual stream, so each of the 16 blocks adds a vector of
rms ~1, and the attention blocks' (averages over thousands of keys) are
much the same at every position. The token has to stay most of the
stream or every position carries the same vector and a greedy stream
loops (reference/keye_vl2.py has the lesson): E sqrt(D) has rms 0.25 x
45.25 = 11.3 against the ~4-6 that sixteen unit vectors sum to, so the
token stays about nine tenths of the stream by norm.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.qwen3 import (HIGHEST, _key, _mm as _mm_control,
                                       _normal, _rms)

_EMBED_STD = 0.25       # module docstring, "Why the embedding"


def _bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _mm(x, w, precision):
    if precision == "bf16":
        return jnp.matmul(_bf16(x), _bf16(w), precision=HIGHEST)
    return _mm_control(x, w, precision)


def sizes(cfg: dict) -> dict:
    dep = cfg["deployment"]
    held = int(cfg["num_experts"])
    L = int(cfg["num_hidden_layers"])
    return dict(
        D=cfg["hidden_size"], I=cfg["intermediate_size"],
        F=cfg["moe_intermediate_size"], L=L,
        dense=int(cfg["num_dense_layers"]),
        Hq=cfg["num_attention_heads"], Hkv=cfg["num_key_value_heads"],
        hd=cfg["head_dim"], W=int(cfg["sliding_window"]),
        every=int(cfg["global_attn_every_n_layers"]),
        kinds=tuple(cfg["layer_types"][:L]),
        E=int(dep["routed_experts_total"]), held=held,
        first=int(dep["ep_rank"]) * held,
        k=int(cfg["num_experts_per_tok"]),
        shared=int(cfg["num_shared_experts"]),
        route_scale=float(cfg["route_scale"]), V=cfg["vocab_size"],
        theta=float(cfg["rope_theta"]), eps=float(cfg["rms_norm_eps"]),
        dtype=jnp.dtype({"bfloat16": jnp.bfloat16,
                         "float32": jnp.float32}[cfg["torch_dtype"]]))


def layer_kind(cfg: dict, li: int):
    """("swa" | "full", "dense" | "moe") of layer li."""
    attn = {"sliding_attention": "swa",
            "full_attention": "full"}[cfg["layer_types"][li]]
    return attn, ("dense" if li < int(cfg["num_dense_layers"]) else "moe")


# ----------------------------------------------------------------------
# rotary tables
# ----------------------------------------------------------------------

def rope_tables(cfg: dict, n: int):
    """(cos, sin) [n, head_dim / 2] float32 at positions 0 .. n - 1."""
    hd, theta = cfg["head_dim"], float(cfg["rope_theta"])
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    ang = np.arange(n, dtype=np.float64)[:, None] * inv
    return (jnp.asarray(np.cos(ang), jnp.float32),
            jnp.asarray(np.sin(ang), jnp.float32))


def _rope(x, cos, sin):
    """x [S, h, d]; cos/sin [S, d / 2]; dim i pairs with dim i + d / 2."""
    x1, x2 = jnp.split(x, 2, axis=-1)
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


def _layer_weights(key, *, ffn, D, I, F, Hq, Hkv, hd, E, held, first,
                   shared, dtype):
    ks = iter(jax.random.split(key, 24))
    nq, nkv = Hq * hd, Hkv * hd
    w = {"ln_in": _near_one(next(ks), D, dtype),
         "ln_post_attn": _near_one(next(ks), D, dtype),
         "ln_pre_mlp": _near_one(next(ks), D, dtype),
         "ln_post_mlp": _near_one(next(ks), D, dtype),
         "wq": _normal(next(ks), (D, nq), D ** -0.5, dtype),
         "wk": _normal(next(ks), (D, nkv), D ** -0.5, dtype),
         "wv": _normal(next(ks), (D, nkv), D ** -0.5, dtype),
         "wg": _normal(next(ks), (D, nq), D ** -0.5, dtype),
         "wo": _normal(next(ks), (nq, D), nq ** -0.5, dtype),
         "q_norm": _near_one(next(ks), hd, dtype),
         "k_norm": _near_one(next(ks), hd, dtype)}
    if ffn == "dense":
        w.update(w_gate=_normal(next(ks), (D, I), D ** -0.5, dtype),
                 w_up=_normal(next(ks), (D, I), D ** -0.5, dtype),
                 w_down=_normal(next(ks), (I, D), I ** -0.5, dtype))
        return w
    Fs = shared * F
    w.update(
        w_router=_normal(next(ks), (D, E), D ** -0.5, dtype),
        e_bias=_normal(next(ks), (E,), 0.02, jnp.float32),
        ws_gate=_normal(next(ks), (D, Fs), D ** -0.5, dtype),
        ws_up=_normal(next(ks), (D, Fs), D ** -0.5, dtype),
        ws_down=_normal(next(ks), (Fs, D), Fs ** -0.5, dtype))
    ek = next(ks)
    eks = jax.vmap(lambda e: jax.random.fold_in(ek, e))(
        first + jnp.arange(held))
    g, u, d = jax.vmap(functools.partial(_expert, D=D, F=F, dtype=dtype))(
        eks)
    w.update(we_gate=g, we_up=u, we_down=d)   # [held, D, F] x 2, [held, F, D]
    return w


_WEIGHT_KEYS = ("D", "I", "F", "Hq", "Hkv", "hd", "E", "held", "first",
                "shared", "dtype")


def layer_weights_fn(cfg: dict, ffn: str, out_sharding=None):
    """A jitted `key -> layer dict` for this configuration and share
    (the attention kinds hold the same matrices)."""
    s = sizes(cfg)
    fn = functools.partial(_layer_weights, ffn=ffn,
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
            k, (s["V"], s["D"]), _EMBED_STD, s["dtype"]))(ks[0])),
        "final_norm": jit(lambda k: _near_one(k, s["D"], s["dtype"]))(
            ks[1]),
        "lm_head": jax.block_until_ready(jit(lambda k: _normal(
            k, (s["D"], s["V"]), 0.02, s["dtype"]))(ks[2]))}


# ----------------------------------------------------------------------
# the forward pass (one sequence: x [S, D])
# ----------------------------------------------------------------------

def route(m, w_router, e_bias, *, k, route_scale):
    """Sigmoid scores over ALL the experts the router has columns for;
    selection reads score + bias, the weights the unbiased scores:
    (weights [S, k], expert numbers [S, k]). float32 always."""
    sc = jax.nn.sigmoid(jnp.matmul(m, w_router, precision=HIGHEST))
    _, idx = jax.lax.top_k(sc + e_bias, k)
    w = jnp.take_along_axis(sc, idx, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * route_scale
    return w, idx.astype(jnp.int32)


def _swiglu(u, g, up, d, precision):
    return _mm(jax.nn.silu(_mm(u, g, precision)) * _mm(u, up, precision),
               d, precision)


def routed_share(m, w, s, precision, with_held: bool = False):
    """What the held experts add for m [S, D]: every held expert over
    every token, times its routing weight (zero where it was not
    chosen). with_held: also which held experts each token chose, as a
    bit mask [S] uint32."""
    wts, idx = route(m, w["w_router"], w["e_bias"], k=s["k"],
                     route_scale=s["route_scale"])

    def one(acc, ew):
        e, g, up, d = ew
        gate = jnp.sum(jnp.where(idx == e, wts, 0.0), axis=-1)
        return acc + gate[..., None] * _swiglu(m, g, up, d, precision), None

    held_ids = s["first"] + jnp.arange(s["held"])
    acc, _ = jax.lax.scan(
        one, jnp.zeros_like(m),
        (held_ids, w["we_gate"], w["we_up"], w["we_down"]))
    if not with_held:
        return acc
    chose = jnp.any(idx[..., None] == held_ids, axis=-2)     # [S, held]
    bits = jnp.sum(jnp.where(
        chose, jnp.uint32(1) << (jnp.arange(s["held"], dtype=jnp.uint32)
                                 % 32), jnp.uint32(0)), axis=-1)
    return acc, bits


def scores(cfg: dict, a_kind: str, u, w, precision="f32"):
    """The attention scores q k^T 128^-0.5 [Hq, S, S] of one layer's
    queries and keys over u [S, D], unmasked: for tests at a small
    size."""
    s = sizes(cfg)
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    q, k, _, _ = _qkvg(u, w, rope_tables(cfg, u.shape[0]), s, a_kind,
                       precision)
    kq = jnp.repeat(k, s["Hq"] // s["Hkv"], axis=1)
    return jnp.einsum("qhd,khd->hqk", q, kq, precision=HIGHEST) \
        * s["hd"] ** -0.5


def _qkvg(u, w, rope, s, a_kind, precision):
    S = u.shape[0]
    Hq, Hkv, hd = s["Hq"], s["Hkv"], s["hd"]
    cached = _bf16 if precision == "bf16" else (lambda t: t)
    q = _rms(_mm(u, w["wq"], precision).reshape(S, Hq, hd), w["q_norm"],
             s["eps"])
    k = _rms(_mm(u, w["wk"], precision).reshape(S, Hkv, hd), w["k_norm"],
             s["eps"])
    v = _mm(u, w["wv"], precision).reshape(S, Hkv, hd)
    if a_kind == "swa":
        q, k = _rope(q, *rope), _rope(k, *rope)
    return cached(q), cached(k), cached(v), _mm(u, w["wg"], precision)


def _attn(u, w, rope, s, a_kind, precision, block_rows):
    """Attn(u) for u [S, D]."""
    S = u.shape[0]
    Hq, Hkv, hd = s["Hq"], s["Hkv"], s["hd"]
    q, k, v, g = _qkvg(u, w, rope, s, a_kind, precision)
    grp = Hq // Hkv
    nb = -(-S // block_rows)
    Sp = nb * block_rows
    qb = jnp.pad(q, ((0, Sp - S), (0, 0), (0, 0))).reshape(
        nb, block_rows, Hkv, grp, hd)
    col = jnp.arange(S)[None, :]

    def block(xs):
        qr, t0 = xs
        t = (t0 + jnp.arange(block_rows))[:, None]
        see = col <= t
        if a_kind == "swa":
            see = see & (col > t - s["W"])
        sc = jnp.einsum("qhgd,khd->hgqk", qr, k,
                        precision=HIGHEST) * (hd ** -0.5)
        p = jax.nn.softmax(jnp.where(see[None, None], sc, -jnp.inf), -1)
        return jnp.einsum("hgqk,khd->qhgd", p, v,
                          precision=HIGHEST).reshape(-1, Hq * hd)

    o = jax.lax.map(block, (qb, jnp.arange(nb) * block_rows))
    o = o.reshape(Sp, Hq * hd)[:S] * jax.nn.sigmoid(g)
    return _mm(o, w["wo"], precision)


def _layer(x, w, rope, *, a_kind, f_kind, skey, precision, block_rows):
    """(the layer's output [S, D]; per position [S] uint32: the held
    experts it chose, 0 in a dense layer)."""
    s = dict(skey)
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    stream = _bf16 if precision == "bf16" else (lambda t: t)
    a = _attn(_rms(x, w["ln_in"], s["eps"]), w, rope, s, a_kind, precision,
              block_rows)
    x = stream(x + _rms(a, w["ln_post_attn"], s["eps"]))
    m = _rms(x, w["ln_pre_mlp"], s["eps"])
    if f_kind == "dense":
        y = _swiglu(m, w["w_gate"], w["w_up"], w["w_down"], precision)
        held = jnp.zeros((x.shape[0],), jnp.uint32)
    else:
        routed, held = routed_share(m, w, s, precision, with_held=True)
        y = _swiglu(m, w["ws_gate"], w["ws_up"], w["ws_down"],
                    precision) + routed
    return stream(x + _rms(y, w["ln_post_mlp"], s["eps"])), held


@functools.lru_cache(maxsize=None)
def _layer_fn(a_kind, f_kind, precision, skey, block_rows):
    return jax.jit(functools.partial(
        _layer, a_kind=a_kind, f_kind=f_kind, skey=skey,
        precision=precision, block_rows=block_rows))


def _skey(s):
    return tuple(sorted((k, v) for k, v in s.items() if k != "dtype"))


def layer_forward(cfg: dict, li: int, x, w, rope, precision="f32",
                  with_held: bool = False, block_rows: int = 128):
    """Layer li of this share over x [S, D] float32."""
    out = _layer_fn(*layer_kind(cfg, li), precision, _skey(sizes(cfg)),
                    min(block_rows, x.shape[0]))(x, w, rope)
    return out if with_held else out[0]


def _weights_fns(cfg, out_sharding=None):
    return {f: layer_weights_fn(cfg, f, out_sharding)
            for f in ("dense", "moe")}


def embed(cfg: dict, table, ids):
    """E[ids] sqrt(D), float32."""
    return table[ids].astype(jnp.float32) * float(cfg["hidden_size"]) ** 0.5


def all_logits(cfg: dict, seed: int, ids, precision: str = "f32"):
    """float32 logits [S, V] of every position of one short sequence."""
    s = sizes(cfg)
    ids = np.asarray(ids, np.int32)
    rope = rope_tables(cfg, len(ids))
    hw = head_weights(cfg, seed)
    x = embed(cfg, hw["embed"], ids)
    fns = _weights_fns(cfg)
    for li in range(s["L"]):
        w = fns[layer_kind(cfg, li)[1]](layer_key(seed, li))
        x = layer_forward(cfg, li, x, w, rope, precision)
    h = _rms(x, hw["final_norm"].astype(jnp.float32), s["eps"])
    return _mm(h, hw["lm_head"].astype(jnp.float32), precision)


def _logit_rows(hidden, head, rows, precision):
    return _mm(hidden[rows], head, precision)


def served_token_gaps(cfg: dict, seed: int, sequences, prompt_lens, *,
                      precisions=("f32",), device=None, block_rows=256,
                      pad_to=512):
    """`compare.py`'s contract, as `qwen3.served_token_gaps` states it:
    for every served token, how far its logit lies below the
    reference's best at that position; for a control precision, the
    same gap of the token that precision's own pass puts first. A
    layer's weights are made, used on every sequence and dropped; one
    sequence at a time."""
    s = sizes(cfg)
    device = device or jax.devices()[0]
    S = -(-max(len(q) for q in sequences) // pad_to) * pad_to
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    with jax.default_device(device):
        rope = rope_tables(cfg, S)
        hw = head_weights(cfg, seed)
        hidden = {p: [] for p in precisions}
        for q in sequences:
            ids = np.zeros((S,), np.int32)
            ids[:len(q)] = np.asarray(q, np.int32)
            x = embed(cfg, hw["embed"], ids)
            for p in precisions:
                hidden[p].append(x)
        # positions at which a lower precision's pass chose another set
        # of held experts than the float32 pass, in any layer
        flipped = {p: [np.zeros((S,), bool) for _ in sequences]
                   for p in precisions if p != "f32"}
        fns = _weights_fns(cfg)
        for li in range(s["L"]):
            w = fns[layer_kind(cfg, li)[1]](layer_key(seed, li))
            for j in range(len(sequences)):
                held = {}
                for p in precisions:
                    hidden[p][j], h = layer_forward(
                        cfg, li, hidden[p][j], w, rope, p, with_held=True,
                        block_rows=block_rows)
                    held[p] = np.asarray(h)
                for p, marks in flipped.items():
                    marks[j] |= held[p] != held["f32"]
            del w
        final_w, lm = f32(hw["final_norm"]), f32(hw["lm_head"])
        del hw
        out = {p: [] for p in precisions}
        rows_fn = jax.jit(_logit_rows, static_argnames=("precision",))
        for i, (q, n0) in enumerate(zip(sequences, prompt_lens)):
            pos = np.arange(n0 - 1, len(q) - 1)
            served = np.asarray(q[n0:], np.int32)
            gaps = {p: [] for p in precisions}
            h = {p: _rms(hidden[p][i], final_w, s["eps"])
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
            at = [marks[i][n0 - 1:len(q) - 1]
                  for i, (q, n0) in enumerate(zip(sequences, prompt_lens))]
            at = np.concatenate(at) if at else np.zeros((0,), bool)
            if at.size:
                print(f"held-expert set differs from float32's ({p} pass "
                      f"of the reference, any layer) at {int(at.sum())} of "
                      f"{len(at)} served positions = "
                      f"{100.0 * at.mean():.2f} %", flush=True)
        # for reading a run by hand: how many distinct tokens a served
        # stream holds (a stream that loops measures near-ties), and
        # whether the gap grows along a stream (a cache or position
        # fault) or not (rounding, a flipped set)
        served = [np.asarray(q[n0:]) for q, n0 in zip(sequences, prompt_lens)
                  if len(q) > n0]
        if served:
            print("distinct tokens of a served stream: " + " ".join(
                f"{len(np.unique(t))}/{len(t)}" for t in served), flush=True)
        for p in precisions:
            fifths = [np.array_split(g, 5) for g in out[p] if g.size >= 5]
            if fifths:
                print(f"gap by fifth of the served stream ({p}): " + " ".join(
                    f"{np.mean(np.concatenate([f[i] for f in fifths])):.5f}"
                    for i in range(5)), flush=True)
    return out
