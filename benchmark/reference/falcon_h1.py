"""Plain reference for Falcon-H1 (`model_type: falcon_h1`,
tiiuae/Falcon-H1-34B-Instruct) as the one-chip stage the configuration
states, and the benchmark's weights for it.

Straightforward `jax.numpy`, float32, matmuls at `highest` precision:
attention over full [rows, S] masks in blocks of query rows so that
~4,600 positions fit; the state-space recurrence SEQUENTIAL, one
position at a time (`lax.scan`), in the published [heads, head_dim,
state] layout, independent of the chunked algebra of the program's
kernels/ssd.py; no cache, no kernel, no batching, one sequence at a
time. It imports nothing of the program (the matmul, its controls'
rounding and the seeded normal are `reference/qwen3.py`'s).

Equations (config.json of tiiuae/Falcon-H1-34B-Instruct as the catalog
carries it, and the published `modeling_falcon_h1.py`), x a layer's
input, D `hidden_size`, eps `rms_norm_eps`; every multiplier as
published, applied where the published code applies it:
    x = E[ids] * embedding_multiplier
    h = RMSNorm_in(x)
    x = x + Mamba(h) * ssm_out_multiplier
          + Attn(h * attention_in_multiplier) * attention_out_multiplier
    x = x + MLP(RMSNorm_ff(x))
    logits = (W_head RMSNorm_f(x)) * lm_head_multiplier
  Mamba (`FalconH1Mixer`, H = `mamba_n_heads` heads of P =
        `mamba_d_head`, G = `mamba_n_groups`, N = `mamba_d_state`,
        Ei = `mamba_d_ssm` = H P):
        p = (W_in (h * ssm_in_multiplier)) * mup, mup scaling the five
        segments z [Ei], x [Ei], B [G N], C [G N], dt [H] by the five
        `ssm_multipliers`;
        xBC = silu(causal depthwise conv of width `mamba_d_conv` + b);
        dt = softplus(dt + dt_bias); A = -exp(A_log), a head;
        head m reads group m // (H / G) of B and C;
        S_m = exp(dt_m A_m) S_m + dt_m x_m B^T  ([P, N], from zero);
        y_m = S_m C + D_m x_m;
        Mamba = W_out RMSNorm_G(y * silu(z)): `mamba_rms_norm`, the norm
        after the gate (`mamba_norm_before_gate` false), over each of
        the G groups of Ei / G channels, one weight of Ei.
  Attn  q = W_q a; k = (W_k a) * key_multiplier; v = W_v a; no biases,
        no QK-norm; rotary on all head_dim dims of q and k, half-split
        (dim i with dim i + d/2), `rope_theta`; causal softmax at scale
        head_dim**-0.5, GQA; Attn = W_o o.
  MLP   W_down(W_up u * silu((W_gate u) * m_gate)) * m_down
        (`mlp_multipliers` = [m_gate, m_down]).

Weights (random from --seed; each departure from the published model is
here): a matrix followed by a multiplier m (W_in's segments, W_out, W_k,
W_o, W_gate, W_down, the head; `attention_in_multiplier` is 1) is normal
with std fan_in**-0.5 / m, every other matrix fan_in**-0.5, so that the
matrix and its multiplier together have std fan_in**-0.5, as the
trained checkpoint's matrices are sized to their multipliers. Drawn at
fan_in**-0.5 itself, attention's output would reach the stream at
0.0375 x 0.011 of its scale and the MLP's at 0.18 x 0.011: no fault of
the cache or of a position could move a logit. RMSNorm weights 1 + 0.1
normal; the conv taps normal std 0.5 (fan-in 4), its bias 0.02 normal;
A_log = log(1 .. H) and D = 1 (the published init); dt_bias the inverse
softplus of a log-uniform draw on [0.001, 0.1] (the published
time_step_min / max), all five float32; every other leaf in the
configuration's dtype. EMBEDDING std 0.5, so E[ids] * 5.66 has rms 2.8:
each layer adds a Mamba output and an MLP output of rms ~1, and four
layers leave the token about two thirds of the stream by norm, so a
greedy stream depends on its token and does not loop
(reference/keye_vl2.py has the lesson).

Controls (`precision`): "int8" / "fp8" round both inputs of every
matmul (`qwen3._mm`); "bf16", the precision the configuration states
for weights and activations, rounds them to bfloat16 and rounds the
residual stream and what the program would cache (k after its rotary,
v) too, its state staying float32 as the configuration states;
"bf16_state" is float32 everywhere but the SSM state, rounded to
bfloat16 after every update (what a bfloat16 state plane would carry).
Scores, softmax, the gate, the norms and the recurrence stay float32 in
every precision but the last.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.qwen3 import (HIGHEST, _key, _mm as _mm_control,
                                       _normal, _rms)

_EMBED_STD = 0.5        # module docstring, "EMBEDDING"


def _bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _mm(x, w, precision):
    if precision == "bf16":
        return jnp.matmul(_bf16(x), _bf16(w), precision=HIGHEST)
    if precision == "bf16_state":
        precision = "f32"
    return _mm_control(x, w, precision)


def sizes(cfg: dict) -> dict:
    H, P = int(cfg["mamba_n_heads"]), int(cfg["mamba_d_head"])
    G, N = int(cfg["mamba_n_groups"]), int(cfg["mamba_d_state"])
    assert H * P == int(cfg["mamba_d_ssm"])
    assert cfg.get("attn_layer_indices") is None   # both mixers a layer
    return dict(
        D=cfg["hidden_size"], I=cfg["intermediate_size"],
        L=int(cfg["num_hidden_layers"]), Hq=cfg["num_attention_heads"],
        Hkv=cfg["num_key_value_heads"], hd=cfg["head_dim"], H=H, P=P,
        G=G, N=N, K=int(cfg["mamba_d_conv"]), V=cfg["vocab_size"],
        theta=float(cfg["rope_theta"]), eps=float(cfg["rms_norm_eps"]),
        emb=float(cfg["embedding_multiplier"]),
        lm=float(cfg["lm_head_multiplier"]),
        a_in=float(cfg["attention_in_multiplier"]),
        a_out=float(cfg["attention_out_multiplier"]),
        key=float(cfg["key_multiplier"]),
        s_in=float(cfg["ssm_in_multiplier"]),
        s_out=float(cfg["ssm_out_multiplier"]),
        mup=tuple(float(m) for m in cfg["ssm_multipliers"]),
        mlp=tuple(float(m) for m in cfg["mlp_multipliers"]),
        dtype=jnp.dtype({"bfloat16": jnp.bfloat16,
                         "float32": jnp.float32}[cfg["torch_dtype"]]))


def mup_vector(s: dict):
    """The five `ssm_multipliers` over W_in's columns z | x | B | C |
    dt (the published `compute_mup_vector`)."""
    Ei, GN = s["H"] * s["P"], s["G"] * s["N"]
    return jnp.concatenate([jnp.full((n,), m, jnp.float32) for n, m in
                            zip((Ei, Ei, GN, GN, s["H"]), s["mup"])])


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


def _layer_weights(key, *, D, I, Hq, Hkv, hd, H, P, G, N, K, a_in, a_out,
                   key_mult, s_in, s_out, mup, mlp, dtype):
    ks = iter(jax.random.split(key, 24))
    nq, nkv, Ei, GN = Hq * hd, Hkv * hd, H * P, G * N
    C = Ei + 2 * GN
    f32 = jnp.float32
    # W_in's columns, each divided by the multiplier that follows it
    col = 1.0 / (s_in * mup_vector(dict(H=H, P=P, G=G, N=N, mup=mup)))
    w_in = (jax.random.normal(next(ks), (D, Ei + C + H), f32)
            * (D ** -0.5) * col).astype(dtype)
    dt0 = jnp.exp(jax.random.uniform(next(ks), (H,), f32,
                                     np.log(1e-3), np.log(1e-1)))
    return {
        "ln_in": _near_one(next(ks), D, dtype),
        "ln_ff": _near_one(next(ks), D, dtype),
        "wq": _normal(next(ks), (D, nq), D ** -0.5 / a_in, dtype),
        "wk": _normal(next(ks), (D, nkv), D ** -0.5 / (a_in * key_mult),
                      dtype),
        "wv": _normal(next(ks), (D, nkv), D ** -0.5 / a_in, dtype),
        "wo": _normal(next(ks), (nq, D), nq ** -0.5 / a_out, dtype),
        "w_in": w_in,
        "conv_w": _normal(next(ks), (K, C), K ** -0.5, dtype),
        "conv_b": _normal(next(ks), (C,), 0.02, f32),
        "dt_bias": dt0 + jnp.log(-jnp.expm1(-dt0)),
        "A_log": jnp.log(jnp.arange(1, H + 1, dtype=f32)),
        "D": jnp.ones((H,), f32),
        "ssm_norm": _near_one(next(ks), Ei, dtype),
        "w_out": _normal(next(ks), (Ei, D), Ei ** -0.5 / s_out, dtype),
        "w_gate": _normal(next(ks), (D, I), D ** -0.5 / mlp[0], dtype),
        "w_up": _normal(next(ks), (D, I), D ** -0.5, dtype),
        "w_down": _normal(next(ks), (I, D), I ** -0.5 / mlp[1], dtype)}


def layer_weights_fn(cfg: dict, out_sharding=None):
    """A jitted `key -> layer dict` for this configuration (every layer
    holds the same tensors)."""
    s = sizes(cfg)
    fn = functools.partial(
        _layer_weights, D=s["D"], I=s["I"], Hq=s["Hq"], Hkv=s["Hkv"],
        hd=s["hd"], H=s["H"], P=s["P"], G=s["G"], N=s["N"], K=s["K"],
        a_in=s["a_in"], a_out=s["a_out"], key_mult=s["key"],
        s_in=s["s_in"], s_out=s["s_out"], mup=s["mup"], mlp=s["mlp"],
        dtype=s["dtype"])
    return jax.jit(fn, out_shardings=out_sharding)


def layer_key(seed: int, li: int):
    return jax.random.fold_in(_key(seed), li)


def head_key(seed: int):
    return jax.random.fold_in(_key(seed), 1 << 20)


def head_weights(cfg: dict, seed: int, out_sharding=None) -> dict:
    """{"embed" [V, D], "final_norm" [D], "lm_head" [D, V]}, as
    published (no multiplier applied). Each big leaf is a jitted call of
    its own, run one after the other."""
    s = sizes(cfg)
    ks = jax.random.split(head_key(seed), 3)
    jit = lambda f: jax.jit(f, out_shardings=out_sharding)  # noqa: E731
    return {
        "embed": jax.block_until_ready(jit(lambda k: _normal(
            k, (s["V"], s["D"]), _EMBED_STD, s["dtype"]))(ks[0])),
        "final_norm": jit(lambda k: _near_one(k, s["D"], s["dtype"]))(
            ks[1]),
        "lm_head": jax.block_until_ready(jit(lambda k: _normal(
            k, (s["D"], s["V"]), s["D"] ** -0.5 / s["lm"],
            s["dtype"]))(ks[2]))}


# ----------------------------------------------------------------------
# the forward pass (one sequence: x [S, D])
# ----------------------------------------------------------------------

def gated_norm(y, z, w, groups: int, eps: float):
    """RMSNorm_G(y * silu(z)) over [S, Ei]: each of `groups` slices of
    Ei / G channels normalised on its own, one weight of Ei."""
    g = y * jax.nn.silu(z)
    S, Ei = g.shape
    g = g.reshape(S, groups, Ei // groups)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + eps)
    return g.reshape(S, Ei) * w


def ssm_recurrence(x, dt, Bm, Cm, A, D, state_dtype=jnp.float32):
    """x [S, H, P], dt [S, H], Bm, Cm [S, G, N], A, D [H]: the published
    recurrence one position at a time, the state [H, P, N] from zero
    (rounded to `state_dtype` after every update). Returns y [S, H, P]."""
    H, P = x.shape[1], x.shape[2]
    N = Bm.shape[2]
    rep = H // Bm.shape[1]

    def step(s, inp):
        x_t, dt_t, b_t, c_t = inp
        b_t = jnp.repeat(b_t, rep, axis=0)                   # [H, N]
        c_t = jnp.repeat(c_t, rep, axis=0)
        s = (jnp.exp(dt_t * A)[:, None, None] * s.astype(jnp.float32)
             + dt_t[:, None, None] * x_t[:, :, None] * b_t[:, None, :])
        s = s.astype(state_dtype)
        y = jnp.sum(s.astype(jnp.float32) * c_t[:, None, :], -1) \
            + D[:, None] * x_t
        return s, y

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), state_dtype),
                        (x, dt, Bm, Cm))
    return y


def _mamba(h, w, s, precision):
    S = h.shape[0]
    H, P, G, N, K = s["H"], s["P"], s["G"], s["N"], s["K"]
    Ei, GN = H * P, G * N
    p = _mm(h * s["s_in"], w["w_in"], precision) * mup_vector(s)
    z, xbc, dt = p[:, :Ei], p[:, Ei:2 * Ei + 2 * GN], p[:, 2 * Ei + 2 * GN:]
    xp = jnp.pad(xbc, ((K - 1, 0), (0, 0)))
    xbc = jax.nn.silu(w["conv_b"] + sum(w["conv_w"][k] * xp[k:k + S]
                                        for k in range(K)))
    x = xbc[:, :Ei].reshape(S, H, P)
    Bm = xbc[:, Ei:Ei + GN].reshape(S, G, N)
    Cm = xbc[:, Ei + GN:].reshape(S, G, N)
    dt = jax.nn.softplus(dt + w["dt_bias"])
    state = jnp.bfloat16 if precision == "bf16_state" else jnp.float32
    y = ssm_recurrence(x, dt, Bm, Cm, -jnp.exp(w["A_log"]), w["D"],
                       state).reshape(S, Ei)
    y = gated_norm(y, z, w["ssm_norm"], G, s["eps"])
    return _mm(y, w["w_out"], precision) * s["s_out"]


def _attn(a, w, rope, s, precision, block_rows):
    S = a.shape[0]
    Hq, Hkv, hd = s["Hq"], s["Hkv"], s["hd"]
    cached = _bf16 if precision == "bf16" else (lambda t: t)
    q = _rope(_mm(a, w["wq"], precision).reshape(S, Hq, hd), *rope)
    k = _rope((_mm(a, w["wk"], precision) * s["key"]).reshape(S, Hkv, hd),
              *rope)
    v = _mm(a, w["wv"], precision).reshape(S, Hkv, hd)
    q, k, v = cached(q), cached(k), cached(v)
    grp = Hq // Hkv
    nb = -(-S // block_rows)
    Sp = nb * block_rows
    qb = jnp.pad(q, ((0, Sp - S), (0, 0), (0, 0))).reshape(
        nb, block_rows, Hkv, grp, hd)
    col = jnp.arange(S)[None, :]

    def block(xs):
        qr, t0 = xs
        see = col <= (t0 + jnp.arange(block_rows))[:, None]
        sc = jnp.einsum("qhgd,khd->hgqk", qr, k,
                        precision=HIGHEST) * (hd ** -0.5)
        p = jax.nn.softmax(jnp.where(see[None, None], sc, -jnp.inf), -1)
        return jnp.einsum("hgqk,khd->qhgd", p, v,
                          precision=HIGHEST).reshape(-1, Hq * hd)

    o = jax.lax.map(block, (qb, jnp.arange(nb) * block_rows))
    return _mm(o.reshape(Sp, Hq * hd)[:S], w["wo"], precision) * s["a_out"]


def _mlp(u, w, s, precision):
    g = _mm(u, w["w_gate"], precision) * s["mlp"][0]
    y = _mm(u, w["w_up"], precision) * jax.nn.silu(g)
    return _mm(y, w["w_down"], precision) * s["mlp"][1]


def _layer(x, w, rope, *, skey, precision, block_rows):
    s = dict(skey)
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    stream = _bf16 if precision == "bf16" else (lambda t: t)
    h = _rms(x, w["ln_in"], s["eps"])
    m = _mamba(h, w, s, precision)
    a = _attn(h * s["a_in"], w, rope, s, precision, block_rows)
    x = stream(x + m + a)
    return stream(x + _mlp(_rms(x, w["ln_ff"], s["eps"]), w, s, precision))


@functools.lru_cache(maxsize=None)
def _layer_fn(precision, skey, block_rows):
    return jax.jit(functools.partial(
        _layer, skey=skey, precision=precision, block_rows=block_rows))


def _skey(s):
    return tuple(sorted((k, v) for k, v in s.items() if k != "dtype"))


def layer_forward(cfg: dict, x, w, rope, precision="f32",
                  block_rows: int = 128):
    """One layer over x [S, D] float32."""
    return _layer_fn(precision, _skey(sizes(cfg)),
                     min(block_rows, x.shape[0]))(x, w, rope)


def embed(cfg: dict, table, ids):
    """E[ids] * embedding_multiplier, float32."""
    return table[ids].astype(jnp.float32) * float(
        cfg["embedding_multiplier"])


def _head(h, lm, s, precision):
    return _mm(h, lm, precision) * s["lm"]


def all_logits(cfg: dict, seed: int, ids, precision: str = "f32"):
    """float32 logits [S, V] of every position of one short sequence."""
    s = sizes(cfg)
    ids = np.asarray(ids, np.int32)
    rope = rope_tables(cfg, len(ids))
    hw = head_weights(cfg, seed)
    x = embed(cfg, hw["embed"], ids)
    fn = layer_weights_fn(cfg)
    for li in range(s["L"]):
        x = layer_forward(cfg, x, fn(layer_key(seed, li)), rope, precision)
    h = _rms(x, hw["final_norm"].astype(jnp.float32), s["eps"])
    return _head(h, hw["lm_head"].astype(jnp.float32), s, precision)


def _logit_rows(hidden, head, rows, precision, scale):
    return _mm(hidden[rows], head, precision) * scale


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
        final_w = hw["final_norm"].astype(jnp.float32)
        lm_b = hw["lm_head"]
        del hw
        fn = layer_weights_fn(cfg)
        for li in range(s["L"]):
            w = fn(layer_key(seed, li))
            for j in range(len(sequences)):
                for p in precisions:
                    hidden[p][j] = layer_forward(cfg, hidden[p][j], w, rope,
                                                 p, block_rows=block_rows)
            del w
        lm = lm_b.astype(jnp.float32)
        del lm_b
        out = {p: [] for p in precisions}
        rows_fn = jax.jit(_logit_rows,
                          static_argnames=("precision", "scale"))
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
                ref = rows_fn(h["f32"], lm, rows, precision="f32",
                              scale=s["lm"])
                best = ref.max(axis=-1)
                tok = np.zeros((block_rows,), np.int32)
                tok[:len(chunk)] = served[r0:r0 + block_rows]
                for p in precisions:
                    pick = jnp.asarray(tok) if p == "f32" else jnp.argmax(
                        rows_fn(h[p], lm, rows, precision=p,
                                scale=s["lm"]), axis=-1)
                    g = best - jnp.take_along_axis(
                        ref, pick[:, None], axis=-1)[:, 0]
                    gaps[p].append(np.asarray(g)[:len(chunk)])
            for p in precisions:
                out[p].append(np.concatenate(gaps[p]) if gaps[p]
                              else np.zeros((0,), np.float32))
        # for reading a run by hand: how many distinct tokens a served
        # stream holds (a stream that loops measures near-ties), and
        # whether the gap grows along a stream (a cache, state or
        # position fault) or not (rounding)
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
