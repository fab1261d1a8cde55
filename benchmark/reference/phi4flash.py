"""Plain reference for Phi-4-mini-flash-reasoning (`model_type:
phi4flash`, the SambaY decoder-hybrid-decoder of arXiv:2507.06607), and
the benchmark's weights for it.

Straightforward `jax.numpy`, float32, matmuls at `highest` precision; the
state-space recurrence is a `lax.scan` over positions, the masks are
full [S, S] matrices, there is no cache and no kernel. It imports
nothing of the program (the matmul, its control's rounding and the
seeded normal are `reference/qwen3.py`'s). Weights are made here from
the seed, a layer at a time.

Equations. Every layer l of the 32, x its input:
    h = x + Mix_l(LN1_l(x));  out = h + W2 (up * silu(gate)),
    [gate | up] = W1 LN2_l(h);  logits = LN_f(x_32) E^T (tied head).
LN is LayerNorm with weight and bias, eps `layer_norm_eps`. There is no
positional encoding of any kind. `Mix_l`, with half = L // 2:
  mamba   l even, l <= half    Mamba-1: [x | z] = Win u; causal depthwise
          conv of width 4 with bias then silu -> xc; [dt_low | B | C] =
          Wx xc; dt = softplus(Wdt dt_low + b_dt); A = -exp(A_log);
          s_t = exp(dt_t A) s_{t-1} + (dt_t xc_t) (x) B_t; y_t = s_t . C_t
          + Dskip xc_t; Mix = Wout (y * silu(z)). Layer `half` also
          hands on its memory M = y (before the gate, with the skip).
  swa     l odd, l < half      differential attention, window 512
  full    l = half + 1         differential attention, causal; its K and
          V are the cache of every cross layer
  cross   l odd, l > half + 1  q = Wq LN1(x) + b only; keys and values
          are layer half+1's; differential; causal
  gmu     l even, l > half     Mix(u) = Wout (M * silu(Win u))
Differential attention: heads pair up (2j, 2j+1), query pair j reads
key/value pair j // (pairs of q / pairs of kv); V = [v1 | v2];
a_i = softmax(q_i k_i^T / sqrt(head) + mask) V;
lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0_l, lam0_l = 0.8 - 0.6
exp(-0.3 l); o = RMSNorm_{2 head}(a1 - lam a2; g, eps 1e-5) (1 - lam0_l).

Departures from the published checkpoint and code: the weights are
random (below); sizes the published config.json does not carry are the
configuration file's `assumed` (d_state 16, d_conv 4, expand 2, dt_rank
ceil(D / 16)). Nothing else is known to differ.

Weights: matrices normal with std fan_in**-0.5 in the configuration's
dtype, embedding 0.02, LayerNorm weights 1 + 0.1 normal and every bias
0.02 normal, as `qwen3.py` scales its own; and, so that the recurrence
stays alive: A_log = log(1..N) in every channel, b_dt the inverse
softplus of a log-uniform draw from [0.001, 0.1], Wdt uniform within
dt_rank**-0.5, Dskip 1 + 0.1 normal, conv weights normal 0.5 (fan_in 4),
the four lambda vectors normal 0.1, the sub-norm's weight 1 + 0.1 normal
(these small vectors stay float32, as the state does).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.qwen3 import HIGHEST, _key, _mm, _normal

SUBLN_EPS = 1e-5


def sizes(cfg: dict) -> dict:
    a = cfg["assumed"]
    D, Hq = cfg["hidden_size"], cfg["num_attention_heads"]
    return dict(
        D=D, I=cfg["intermediate_size"], L=cfg["num_hidden_layers"],
        Hq=Hq, Hkv=cfg["num_key_value_heads"], hd=D // Hq,
        V=cfg["vocab_size"], window=int(cfg["sliding_window"]),
        eps=float(cfg["layer_norm_eps"]),
        N=int(a["mamba_d_state"]), K=int(a["mamba_d_conv"]),
        E=int(a["mamba_expand"]) * D, R=int(a["mamba_dt_rank"]),
        dtype=jnp.dtype({"bfloat16": jnp.bfloat16,
                         "float32": jnp.float32}[cfg["torch_dtype"]]))


def layer_kind(cfg: dict, li: int) -> str:
    half = cfg["num_hidden_layers"] // 2
    if li % 2 == 0:
        return "mamba" if li <= half else "gmu"
    if li < half:
        return "swa"
    return "full" if li == half + 1 else "cross"


def lambda_init(li: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * li)


# ----------------------------------------------------------------------
# weights
# ----------------------------------------------------------------------

def _near_one(key, n):
    return 1.0 + 0.1 * jax.random.normal(key, (n,), jnp.float32)


def _layer_weights(key, *, kind, D, I, Hq, Hkv, hd, E, N, K, R, dtype):
    ks = iter(jax.random.split(key, 32))
    w = {"ln1_w": _near_one(next(ks), D).astype(dtype),
         "ln1_b": _normal(next(ks), (D,), 0.02, dtype),
         "ln2_w": _near_one(next(ks), D).astype(dtype),
         "ln2_b": _normal(next(ks), (D,), 0.02, dtype),
         "w1": _normal(next(ks), (D, 2 * I), D ** -0.5, dtype),
         "w2": _normal(next(ks), (I, D), I ** -0.5, dtype)}
    if kind == "mamba":
        dt = jnp.exp(jax.random.uniform(
            next(ks), (E,), jnp.float32, math.log(1e-3), math.log(1e-1)))
        w.update(
            in_proj=_normal(next(ks), (D, 2 * E), D ** -0.5, dtype),
            conv_w=_normal(next(ks), (K, E), 0.5, jnp.float32),
            conv_b=_normal(next(ks), (E,), 0.02, jnp.float32),
            x_proj=_normal(next(ks), (E, R + 2 * N), E ** -0.5, dtype),
            dt_w=jax.random.uniform(next(ks), (R, E), jnp.float32,
                                    -R ** -0.5, R ** -0.5).astype(dtype),
            dt_b=dt + jnp.log(-jnp.expm1(-dt)),
            A_log=jnp.broadcast_to(
                jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32)), (E, N)),
            Dskip=_near_one(next(ks), E),
            out_proj=_normal(next(ks), (E, D), E ** -0.5, dtype))
    elif kind == "gmu":
        w.update(win=_normal(next(ks), (D, E), D ** -0.5, dtype),
                 wout=_normal(next(ks), (E, D), E ** -0.5, dtype))
    else:
        nq, nkv = Hq * hd, Hkv * hd
        cols = nq if kind == "cross" else nq + 2 * nkv
        w.update(
            wqkv=_normal(next(ks), (D, cols), D ** -0.5, dtype),
            bqkv=_normal(next(ks), (cols,), 0.02, dtype),
            wo=_normal(next(ks), (nq, D), nq ** -0.5, dtype),
            bo=_normal(next(ks), (D,), 0.02, dtype),
            lam=_normal(next(ks), (4, hd), 0.1, jnp.float32),
            subln=_near_one(next(ks), 2 * hd))
    return w


def layer_weights_fn(cfg: dict, kind: str, out_sharding=None):
    """A jitted `key -> layer dict` for one kind of layer of this
    configuration (five small compiles make all 32 layers)."""
    s = sizes(cfg)
    fn = functools.partial(
        _layer_weights, kind=kind,
        **{k: s[k] for k in ("D", "I", "Hq", "Hkv", "hd", "E", "N", "K",
                             "R", "dtype")})
    return jax.jit(fn, out_shardings=out_sharding)


def layer_key(seed: int, li: int):
    return jax.random.fold_in(_key(seed), li)


def head_key(seed: int):
    return jax.random.fold_in(_key(seed), 1 << 20)


def head_weights(cfg: dict, seed: int, out_sharding=None) -> dict:
    """{"embed" [V, D], "lnf_w", "lnf_b"}; the head is the embedding."""
    s = sizes(cfg)
    ks = jax.random.split(head_key(seed), 3)
    jit = lambda f: jax.jit(f, out_shardings=out_sharding)  # noqa: E731
    return {
        "embed": jax.block_until_ready(jit(lambda k: _normal(
            k, (s["V"], s["D"]), 0.02, s["dtype"]))(ks[0])),
        "lnf_w": jit(lambda k: _near_one(k, s["D"]).astype(s["dtype"]))(
            ks[1]),
        "lnf_b": jit(lambda k: _normal(k, (s["D"],), 0.02, s["dtype"]))(
            ks[2])}


# ----------------------------------------------------------------------
# the forward pass
# ----------------------------------------------------------------------

def _ln(x, w, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def _mamba(u, w, *, N, K, R, precision):
    """u [B, S, D] -> (mix [B, S, D], memory y [B, S, E])."""
    B, S, _ = u.shape
    xz = _mm(u, w["in_proj"], precision)
    x, z = jnp.split(xz, 2, axis=-1)
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    xc = w["conv_b"] + sum(w["conv_w"][k] * xp[:, k:k + S]
                           for k in range(K))
    xc = jax.nn.silu(xc)
    low = _mm(xc, w["x_proj"], precision)
    dt = jax.nn.softplus(_mm(low[..., :R], w["dt_w"], precision)
                         + w["dt_b"])
    Bm, Cm = low[..., R:R + N], low[..., R + N:]
    A = -jnp.exp(w["A_log"])                                   # [E, N]

    def step(s, inp):
        xc_t, dt_t, b_t, c_t = inp             # [B, E], [B, E], [B, N] x 2
        s = jnp.exp(dt_t[..., None] * A) * s \
            + (dt_t * xc_t)[..., None] * b_t[:, None, :]
        y = jnp.einsum("ben,bn->be", s, c_t, precision=HIGHEST)
        return s, y + w["Dskip"] * xc_t

    s0 = jnp.zeros((B, x.shape[-1], N), jnp.float32)
    t_first = lambda a: jnp.swapaxes(a, 0, 1)  # noqa: E731
    _, y = jax.lax.scan(step, s0, tuple(map(t_first, (xc, dt, Bm, Cm))))
    y = t_first(y)
    return _mm(y * jax.nn.silu(z), w["out_proj"], precision), y


def _diff_pair(q, k, v, mask, lam, subln, lam0):
    """One sequence, one key/value pair and the g query pairs that read
    it: q [S, g, 2, hd], k [S, 2, hd], v [S, 2 hd] = [v1 | v2], mask
    [S, S] bool. Returns [S, g, 2 hd]."""
    hd = q.shape[-1]

    def softmax_v(qi, ki):                       # [S, g, hd], [S, hd]
        s = jnp.einsum("sgd,td->gst", qi, ki, precision=HIGHEST) \
            * hd ** -0.5
        p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("gst,te->sge", p, v, precision=HIGHEST)

    a = softmax_v(q[:, :, 0], k[:, 0]) - lam * softmax_v(q[:, :, 1],
                                                         k[:, 1])
    a = a * jax.lax.rsqrt(jnp.mean(a * a, axis=-1, keepdims=True)
                          + SUBLN_EPS) * subln
    return a * (1.0 - lam0)


def _attention(u, w, kv, lam0, *, kind, window, Hq, Hkv, hd, precision):
    """u [B, S, D]; kv: layer half+1's (k, v) for a cross layer; lam0:
    the layer's lambda_init. Returns (mix, (k, v))."""
    B, S, _ = u.shape
    qkv = _mm(u, w["wqkv"], precision) + w["bqkv"]
    nq, nkv = Hq * hd, Hkv * hd
    if kind == "cross":
        q, (k, v) = qkv, kv
    else:
        q, k, v = qkv[..., :nq], qkv[..., nq:nq + nkv], qkv[..., nq + nkv:]
    i = jnp.arange(S)
    mask = i[None, :] <= i[:, None]
    if kind == "swa":
        mask = mask & (i[None, :] > i[:, None] - window)
    lq1, lk1, lq2, lk2 = w["lam"]
    lam = jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2)) + lam0
    # heads pair up (2j, 2j+1); query pair j reads key/value pair j // g.
    # One (sequence, key/value pair) at a time: its two [g, S, S] score
    # matrices are what has to fit, not a whole layer's.
    pq, pkv = Hq // 2, Hkv // 2
    g = pq // pkv
    lead = lambda a, *tail: jnp.moveaxis(  # noqa: E731
        a.reshape((B, S, pkv) + tail), 2, 1).reshape((B * pkv, S) + tail)
    o = jax.lax.map(
        lambda a: _diff_pair(*a, mask, lam, w["subln"], lam0),
        (lead(q, g, 2, hd), lead(k, 2, hd), lead(v, 2 * hd)))
    o = jnp.moveaxis(o.reshape(B, pkv, S, g * 2 * hd), 1, 2)
    return _mm(o.reshape(B, S, nq), w["wo"], precision) + w["bo"], (k, v)


def _layer(x, w, aux, lam0, *, kind, s, precision):
    """x [B, S, D] float32; aux: what this layer takes from an earlier
    one beside the residual (a gmu layer the memory M of layer half, a
    cross layer the (k, v) of layer half+1); lam0: an attention layer's
    lambda_init (traced: one program serves every layer of a kind).
    Returns (out, what this layer can hand on: a mamba layer its scan
    output {"M": ...}, the full layer {"kv": ...}, else {})."""
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    u = _ln(x, w["ln1_w"], w["ln1_b"], s["eps"])
    hands_on = {}
    if kind == "mamba":
        mix, y = _mamba(u, w, N=s["N"], K=s["K"], R=s["R"],
                        precision=precision)
        hands_on = {"M": y}
    elif kind == "gmu":
        mix = _mm(aux * jax.nn.silu(_mm(u, w["win"], precision)),
                  w["wout"], precision)
    else:
        mix, kv = _attention(u, w, aux, lam0, kind=kind,
                             window=s["window"], Hq=s["Hq"], Hkv=s["Hkv"],
                             hd=s["hd"], precision=precision)
        if kind == "full":
            hands_on = {"kv": kv}
    h = x + mix
    gu = _mm(_ln(h, w["ln2_w"], w["ln2_b"], s["eps"]), w["w1"], precision)
    gate, up = jnp.split(gu, 2, axis=-1)
    return h + _mm(up * jax.nn.silu(gate), w["w2"], precision), hands_on


@functools.lru_cache(maxsize=None)
def _layer_fn(kind, precision, skey):
    return jax.jit(functools.partial(_layer, kind=kind, s=dict(skey),
                                     precision=precision))


def _run_layer(cfg, s, li, x, w, carry, precision):
    """One layer over x; `carry` ({"M", "kv"} as they appear) grows by
    what the layer hands on."""
    kind = layer_kind(cfg, li)
    skey = tuple(sorted((k, v) for k, v in s.items() if k != "dtype"))
    aux = carry.get({"gmu": "M", "cross": "kv"}.get(kind))
    x, hands_on = _layer_fn(kind, precision, skey)(
        x, w, aux, jnp.float32(lambda_init(li)))
    if kind == "mamba" and li != s["L"] // 2:
        hands_on = {}            # only layer half's memory goes on
    return x, {**carry, **hands_on}


def _weights_fns(cfg):
    return {k: layer_weights_fn(cfg, k)
            for k in ("mamba", "swa", "full", "cross", "gmu")}


def all_logits(cfg: dict, seed: int, ids, precision: str = "f32"):
    """float32 logits [S, V] of every position of one short sequence."""
    s = sizes(cfg)
    ids = np.asarray(ids, np.int32)[None]
    hw = head_weights(cfg, seed)
    x = hw["embed"][ids].astype(jnp.float32)
    fns, carry = _weights_fns(cfg), {}
    for li in range(s["L"]):
        w = fns[layer_kind(cfg, li)](layer_key(seed, li))
        x, carry = _run_layer(cfg, s, li, x, w, carry, precision)
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    h = _ln(x[0], f32(hw["lnf_w"]), f32(hw["lnf_b"]), s["eps"])
    return _mm(h, f32(hw["embed"]).T, precision)


def _logit_rows(hidden, head, rows, precision):
    return _mm(hidden[rows], head, precision)


def served_token_gaps(cfg: dict, seed: int, sequences, prompt_lens, *,
                      precisions=("f32",), device=None, block_rows=256,
                      seq_block=2, pad_to=128):
    """`compare.py`'s contract, as `qwen3.served_token_gaps` states it:
    for every served token, how far its logit lies below the
    reference's best at that position; for a control precision, the
    same gap of the token that precision's own pass puts first. A
    layer's weights are made, used on every block of sequences and
    dropped; two sequences a block keep the [S, 2 I] activations of a
    4,096-token pass near 0.7 GB."""
    s = sizes(cfg)
    device = device or jax.devices()[0]
    n_seq = len(sequences)
    S = -(-max(len(q) for q in sequences) // pad_to) * pad_to
    ids = np.zeros((n_seq, S), np.int32)
    for i, q in enumerate(sequences):
        ids[i, :len(q)] = np.asarray(q, np.int32)
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    with jax.default_device(device):
        hw = head_weights(cfg, seed)
        embed = hw["embed"]
        fns = _weights_fns(cfg)
        blocks = range(0, n_seq, seq_block)
        hidden = {p: [f32(embed[ids[b:b + seq_block]]) for b in blocks]
                  for p in precisions}
        carry = {p: [{} for _ in blocks] for p in precisions}
        for li in range(s["L"]):
            w = fns[layer_kind(cfg, li)](layer_key(seed, li))
            for p in precisions:
                for j in range(len(hidden[p])):
                    hidden[p][j], carry[p][j] = _run_layer(
                        cfg, s, li, hidden[p][j], w, carry[p][j], p)
            del w
        del carry
        lnf_w, lnf_b = f32(hw["lnf_w"]), f32(hw["lnf_b"])
        lm = f32(embed).T
        del hw, embed
        out = {p: [] for p in precisions}
        rows_fn = jax.jit(_logit_rows, static_argnames=("precision",))
        for i, (q, n0) in enumerate(zip(sequences, prompt_lens)):
            pos = np.arange(n0 - 1, len(q) - 1)
            served = np.asarray(q[n0:], np.int32)
            gaps = {p: [] for p in precisions}
            b, j = divmod(i, seq_block)
            h = {p: _ln(hidden[p][b][j], lnf_w, lnf_b, s["eps"])
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
    return out
