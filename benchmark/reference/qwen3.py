"""Plain reference for the Qwen3 dense family, and the benchmark's weights.

Straightforward `jax.numpy`, float32, matmuls at `highest` precision: no
kernel, no cache, no batching tricks. It imports nothing of the program
and takes nothing the program has made: the weights are made here from
the seed (`layer_weights`, `head_weights`), once for the program (which
is handed them in the dtype it serves in) and once more, layer by layer,
for the reference after the window has closed.

Equations (Qwen3, https://huggingface.co/Qwen/Qwen3-1.7B config.json and
the `Qwen3ForCausalLM` description): pre-norm decoder; RMSNorm with a
learned weight; attention with grouped KV heads, a per-head RMSNorm on q
and on k (learned weight over head_dim) before rotary embedding in the
half-split ("rotate_half") layout with theta from the config; causal
softmax at scale head_dim**-0.5; SwiGLU MLP; final RMSNorm; an LM head
that is the embedding transposed where `tie_word_embeddings` is set.
Departure from a checkpoint: weights are random (normal, fan_in**-0.5;
embedding 0.02; norm weights 1 + 0.1 * normal), as the contract allows.

`precision` selects the arithmetic of the matmul inputs:
  "f32"   the reference: float32 at `highest`;
  "fp8"   the control: both inputs of every matmul rounded to
          float8_e4m3fn with a per-row / per-column scale (the nearest
          precision below the bfloat16 the configuration states);
  "int8"  the same with symmetric int8.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def sizes(cfg: dict) -> dict:
    """The sizes the equations need, by the HF config's own key names."""
    return dict(
        D=cfg["hidden_size"], I=cfg["intermediate_size"],
        L=cfg["num_hidden_layers"], Hq=cfg["num_attention_heads"],
        Hkv=cfg["num_key_value_heads"], hd=cfg["head_dim"],
        V=cfg["vocab_size"], theta=float(cfg["rope_theta"]),
        eps=float(cfg["rms_norm_eps"]),
        tied=bool(cfg["tie_word_embeddings"]),
        dtype=jnp.dtype({"bfloat16": jnp.bfloat16,
                         "float32": jnp.float32}[cfg["torch_dtype"]]))


def _key(seed: int):
    return jax.random.key(int(seed))


def _normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def _layer_weights(key, *, D, I, Hq, Hkv, hd, dtype):
    ks = jax.random.split(key, 11)
    near_one = lambda k, n: (  # noqa: E731
        1.0 + 0.1 * jax.random.normal(k, (n,), jnp.float32))
    return {
        "wq": _normal(ks[0], (D, Hq * hd), D ** -0.5, dtype),
        "wk": _normal(ks[1], (D, Hkv * hd), D ** -0.5, dtype),
        "wv": _normal(ks[2], (D, Hkv * hd), D ** -0.5, dtype),
        "wo": _normal(ks[3], (Hq * hd, D), (Hq * hd) ** -0.5, dtype),
        "w_gate": _normal(ks[4], (D, I), D ** -0.5, dtype),
        "w_up": _normal(ks[5], (D, I), D ** -0.5, dtype),
        "w_down": _normal(ks[6], (I, D), I ** -0.5, dtype),
        "ln_attn": near_one(ks[7], D).astype(dtype),
        "ln_mlp": near_one(ks[8], D).astype(dtype),
        "q_norm": near_one(ks[9], hd),
        "k_norm": near_one(ks[10], hd),
    }


# which axis of each big leaf a tensor-parallel deployment splits; the
# system's adapter turns these into shardings so that no chip ever holds
# a whole layer of a model that does not fit one
TP_SPLIT_AXIS = {"wq": 1, "wk": 1, "wv": 1, "w_gate": 1, "w_up": 1,
                 "wo": 0, "w_down": 0}


def layer_weights_fn(cfg: dict, out_shardings=None):
    """A jitted `key -> layer dict` for this configuration. One compiled
    program makes every layer (the key is folded with the layer's
    number), so set-up pays one small compile."""
    s = sizes(cfg)
    fn = functools.partial(_layer_weights, D=s["D"], I=s["I"], Hq=s["Hq"],
                           Hkv=s["Hkv"], hd=s["hd"], dtype=s["dtype"])
    return jax.jit(fn, out_shardings=out_shardings)


def layer_key(seed: int, li: int):
    return jax.random.fold_in(_key(seed), li)


def _embed(key, *, D, V, dtype):
    return _normal(key, (V, D), 0.02, dtype)


def _final_norm(key, *, D, dtype):
    return (1.0 + 0.1 * jax.random.normal(key, (D,), jnp.float32)
            ).astype(dtype)


def _lm_head(key, *, D, V, dtype):
    return _normal(key, (D, V), 0.02, dtype)


def head_weights_fn(cfg: dict, out_sharding=None):
    """`key -> {"embed", "final_norm"[, "lm_head"]}`. Each big leaf is a
    jitted call of its own, run one after the other: made together, the
    float32 normals of a 151,936 x 5,120 embedding and head stood beside
    a chip's share of the layers and filled it (16.30e9 B peak on the
    first TP=4 run, PR 28)."""
    s = sizes(cfg)
    kw = dict(D=s["D"], dtype=s["dtype"])
    jit = lambda f, **k: jax.jit(  # noqa: E731
        functools.partial(f, **kw, **k), out_shardings=out_sharding)
    embed, norm = jit(_embed, V=s["V"]), jit(_final_norm)
    head = None if s["tied"] else jit(_lm_head, V=s["V"])

    def make(key):
        ks = jax.random.split(key, 3)
        out = {"embed": jax.block_until_ready(embed(ks[0])),
               "final_norm": norm(ks[1])}
        if head is not None:
            out["lm_head"] = jax.block_until_ready(head(ks[2]))
        return out
    return make


def head_key(seed: int):
    return jax.random.fold_in(_key(seed), 1 << 20)


# ----------------------------------------------------------------------
# the forward pass
# ----------------------------------------------------------------------

def _fake_quant(x, axis, precision):
    """x rounded to the control's precision along `axis` (the contracted
    axis), with one scale per remaining row or column."""
    if precision == "f32":
        return x
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    amax = jnp.where(amax == 0, 1.0, amax)
    if precision == "fp8":
        scale = amax / 448.0                      # e4m3fn's largest
        q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
        return q * scale
    if precision == "int8":
        scale = amax / 127.0
        return jnp.clip(jnp.round(x / scale), -127, 127) * scale
    raise ValueError(f"unknown precision {precision!r}")


def _mm(x, w, precision):
    """x [..., K] @ w [K, N] in float32 at `highest`; the control rounds
    both inputs first."""
    x = _fake_quant(x, -1, precision)
    w = _fake_quant(w, 0, precision)
    return jnp.matmul(x, w, precision=HIGHEST)


def _rms(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _rope(x, cos, sin):
    """x [B, S, H, hd]; cos/sin [S, hd/2]; half-split rotation."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    c, s = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _layer(x, w, cos, sin, *, Hq, Hkv, hd, eps, precision):
    """One decoder layer over x [B, S, D] float32, full causal attention."""
    B, S, _ = x.shape
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    h = _rms(x, w["ln_attn"], eps)
    q = _mm(h, w["wq"], precision).reshape(B, S, Hq, hd)
    k = _mm(h, w["wk"], precision).reshape(B, S, Hkv, hd)
    v = _mm(h, w["wv"], precision).reshape(B, S, Hkv, hd)
    q = _rope(_rms(q, w["q_norm"], eps), cos, sin)
    k = _rope(_rms(k, w["k_norm"], eps), cos, sin)
    g = Hq // Hkv
    q = q.reshape(B, S, Hkv, g, hd)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q, k, precision=HIGHEST)
    s = s * (hd ** -0.5)
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal[None, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v, precision=HIGHEST)
    x = x + _mm(o.reshape(B, S, Hq * hd), w["wo"], precision)
    h = _rms(x, w["ln_mlp"], eps)
    a = jax.nn.silu(_mm(h, w["w_gate"], precision)) * _mm(
        h, w["w_up"], precision)
    return x + _mm(a, w["w_down"], precision)


@functools.lru_cache(maxsize=None)
def _layer_fn(Hq, Hkv, hd, eps, precision):
    return jax.jit(functools.partial(_layer, Hq=Hq, Hkv=Hkv, hd=hd,
                                     eps=eps, precision=precision))


def _logit_rows(hidden, head, rows, precision):
    """float32 logits [len(rows), V] of the chosen hidden rows."""
    return _mm(hidden[rows], head, precision)


def rope_tables(hd: int, n: int, theta: float):
    inv = 1.0 / (theta ** (np.arange(0, hd, 2) / hd))
    f = np.outer(np.arange(n), inv)
    return jnp.asarray(np.cos(f), jnp.float32), jnp.asarray(
        np.sin(f), jnp.float32)


def all_logits(cfg: dict, seed: int, ids, precision: str = "f32"):
    """float32 logits [S, V] of every position of one short sequence: the
    whole forward pass in one piece, for tests and small readings."""
    s = sizes(cfg)
    ids = np.asarray(ids, np.int32)[None]
    cos, sin = rope_tables(s["hd"], ids.shape[1], s["theta"])
    hw = head_weights_fn(cfg)(head_key(seed))
    x = hw["embed"][ids].astype(jnp.float32)
    lw_fn = layer_weights_fn(cfg)
    f = _layer_fn(s["Hq"], s["Hkv"], s["hd"], s["eps"], precision)
    for li in range(s["L"]):
        x = f(x, lw_fn(layer_key(seed, li)), cos, sin)
    lm = (hw["embed"].T if s["tied"] else hw["lm_head"]).astype(jnp.float32)
    h = _rms(x[0], hw["final_norm"].astype(jnp.float32), s["eps"])
    return _mm(h, lm, precision)


def served_token_gaps(cfg: dict, seed: int, sequences, prompt_lens, *,
                      precisions=("f32",), device=None, block_rows=256,
                      seq_block=8, pad_to=128):
    """For every served token of every sequence, how far its logit lies
    below the reference's best at that position.

    sequences: lists of token ids, prompt then served tokens;
    prompt_lens: the prompt's length in each. The token at position p
    (p >= prompt_len) was chosen from the logits of position p - 1.

    Returns {"f32": gaps} where gaps[i] is a float32 array over sequence
    i's served tokens of `max(ref logits) - ref logits[served token]`.
    With a further precision (the control) in `precisions`, also
    {"fp8": ...}: the same gap, in the float32 reference's logits, of the
    token that the lower precision's own forward pass puts first at each
    of those positions. The weights are made here from the seed, layer by
    layer, and sequences run in blocks, so the pass fits beside nothing.
    """
    s = sizes(cfg)
    device = device or jax.devices()[0]
    n_seq = len(sequences)
    longest = max(len(q) for q in sequences)
    S = -(-longest // pad_to) * pad_to
    ids = np.zeros((n_seq, S), np.int32)
    for i, q in enumerate(sequences):
        ids[i, :len(q)] = np.asarray(q, np.int32)
    cos, sin = rope_tables(s["hd"], S, s["theta"])
    with jax.default_device(device):
        hw = head_weights_fn(cfg)(head_key(seed))
        embed = hw["embed"]
        lw_fn = layer_weights_fn(cfg)
        hidden = {}
        for prec in precisions:
            hidden[prec] = [embed[ids[b:b + seq_block]].astype(jnp.float32)
                            for b in range(0, n_seq, seq_block)]
        for li in range(s["L"]):
            w = lw_fn(layer_key(seed, li))
            for prec in precisions:
                f = _layer_fn(s["Hq"], s["Hkv"], s["hd"], s["eps"], prec)
                hidden[prec] = [f(x, w, cos, sin) for x in hidden[prec]]
            del w
        final_w = hw["final_norm"].astype(jnp.float32)
        lm = (embed.T if s["tied"] else hw["lm_head"]).astype(jnp.float32)
        del hw, embed
        out = {p: [] for p in precisions}
        rows_fn = jax.jit(_logit_rows, static_argnames=("precision",))
        for i, (q, n0) in enumerate(zip(sequences, prompt_lens)):
            pos = np.arange(n0 - 1, len(q) - 1)          # predicting rows
            served = np.asarray(q[n0:], np.int32)
            gaps = {p: [] for p in precisions}
            b, j = divmod(i, seq_block)
            h32 = _rms(hidden["f32"][b][j], final_w, s["eps"])
            hlow = {p: _rms(hidden[p][b][j], final_w, s["eps"])
                    for p in precisions if p != "f32"}
            for r0 in range(0, len(pos), block_rows):
                rows = np.zeros((block_rows,), np.int32)
                chunk = pos[r0:r0 + block_rows]
                rows[:len(chunk)] = chunk
                ref = rows_fn(h32, lm, rows, precision="f32")
                best = ref.max(axis=-1)
                tok = np.zeros((block_rows,), np.int32)
                tok[:len(chunk)] = served[r0:r0 + block_rows]
                g = best - jnp.take_along_axis(
                    ref, jnp.asarray(tok)[:, None], axis=-1)[:, 0]
                gaps["f32"].append(np.asarray(g)[:len(chunk)])
                for p, hl in hlow.items():
                    low = rows_fn(hl, lm, rows, precision=p)
                    first = jnp.argmax(low, axis=-1)
                    g = best - jnp.take_along_axis(
                        ref, first[:, None], axis=-1)[:, 0]
                    gaps[p].append(np.asarray(g)[:len(chunk)])
            for p in precisions:
                out[p].append(np.concatenate(gaps[p]) if gaps[p]
                              else np.zeros((0,), np.float32))
    return out
