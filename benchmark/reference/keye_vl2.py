"""Plain reference for the LANGUAGE MODEL of Keye-VL-2.0-30B-A3B
(`model_type: KeyeVL2`) as ONE CHIP'S SHARE of an expert-parallel
deployment, and the benchmark's weights for it.

Straightforward `jax.numpy`, float32, matmuls at `highest` precision:
full causal attention under the indexer's selection, the selection by
`jax.lax.top_k` on the full score rows, in blocks of query rows so that
20k positions fit; every held expert computed densely over every token
and weighted by its (mostly zero) routing weight; no cache, no kernel,
no grouping. It imports nothing of the program (the matmul, its
controls' rounding and the seeded normal are `reference/qwen3.py`'s).

Equations (config.json of Kwai-Keye/Keye-VL-2.0-30B-A3B as the catalog
carries it; the keys are the Qwen3-MoE family's plus `sa_config` and
`rope_scaling.mrope_section`), x a layer's input, eps 1e-6:
    x = x + Attn(RMSNorm(x));  x = x + Routed(RMSNorm(x))
    final RMSNorm, untied head. No biases.
  Attn  q = a W_q -> [32, 128]; k = a W_k, v = a W_v -> [4, 128];
        q, k <- RMSNorm_128 per head (learned weight); rotary on q, k
        with theta 1e7 in three sections of the 64 frequency pairs:
        pairs 0-15 at position component p[0], 16-39 at p[1], 40-63 at
        p[2] (`mrope_section` [16, 24, 24]); a text token has p[0] =
        p[1] = p[2] = t.
  Indexer (`sa_config`)  qI = a W_qI -> [16, 64]; kI = a W_kI -> [64]
        (ONE key head); w = a W_w -> [16]; rotary (theta as above,
        position p[0], all 64 dims) on qI, kI.
        I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s]) 64^-0.5 16^-0.5.
        S_t = the `topk` positions s <= t of largest I[t, s] (all while
        t < topk); ties go to the lower position (`jax.lax.top_k`).
        o[t, h] = softmax_{s in S_t}(q[t, h] . k[s, h // 8] 128^-0.5)
        v[s, h // 8]: one set S_t for all heads. x <- x + o W_o.
  Routed  softmax(b W_r) over all experts, top-8, renormalised to 1
        (`norm_topk_prob`); sum_e p_e W_down_e(silu(W_gate_e b) W_up_e
        b), expert width 768, no shared expert, every layer an expert
        layer (`decoder_sparse_step` 1, `mlp_only_layers` []).
  Share the chip holds experts first .. first + held - 1 (`held` = the
        file's `num_experts`, `first` = `deployment.ep_rank` x held) of
        `deployment.routed_experts_total`. It routes over all of them
        and adds p_e Expert_e(b) for the chosen experts it holds only;
        what the other chips would add is LEFT OUT, and that partial
        sum goes on to the next layer.

ASSUMED (the config does not carry them; each is in the configuration
file's `assumed`): QK-norm on the main heads and bfloat16 (the
Qwen3-MoE family's convention); rotary on all 64 indexer dims,
half-split pairing (dim i with dim i + half) there and on the main
heads; the two scale factors of I; no normalisation of kI; scores and
top-k in float32. NOT BUILT: the vision tower and its projector (the
catalog has no config for them): token ids in, text positions unless
the caller hands a [3, S] position array.

Controls (`precision`): "int8" / "fp8" round both inputs of every
matmul (`qwen3._mm`); "bf16", the precision the configuration states,
rounds them to bfloat16 and rounds the residual stream and what the
program would cache (q, k, v, qI, kI) too. Scores, softmax and the
selection stay float32 in every precision. "sel_last" keeps float32
everywhere and attends a WRONG set of the right size (the last `topk`
positions): a served path whose selection is broken, which the cell's
limits have to refuse.

Weights: matrices normal with std fan_in**-0.5 in the configuration's
dtype, head 0.02, RMSNorm weights 1 + 0.1 normal, EMBEDDING 1.0. Expert
e's matrices are made from a key folded with its GLOBAL number, so every
share of a layer holds the same experts the uncut layer has.

Why the embedding is not 0.02 like the other families': a softmax
over ~750 effective keys averages the values' idiosyncratic parts down
by sqrt(750) and keeps whatever they have in common whole. Behind a
0.02 embedding six random layers made the residual stream 97 % the
SAME vector at every position from the second layer on (measured, this
file at the published widths on random ids: PERF.md section 6, PR 39):
a greedy stream then repeats 1-30 tokens of its own from its first
step, every logit sits on a near-tie, and the gap statistics rest on
which near-ties a run's sampled streams happen to hold. With a unit
embedding the token stays 85-95 % of the stream, streams run through
~1,500 distinct tokens of 1,570, and a stream's mean gap spreads by a
quarter and not by a hundredfold.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.qwen3 import (HIGHEST, _key, _mm as _mm_control,
                                       _normal, _rms)


def _bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


# A control that keeps float32 and breaks the SELECTION instead: what a
# program whose indexer, index plane or mask went wrong would serve.
BROKEN_SELECTIONS = ("sel_last",)


def _mm(x, w, precision):
    if precision == "bf16":
        return jnp.matmul(_bf16(x), _bf16(w), precision=HIGHEST)
    if precision in BROKEN_SELECTIONS:
        precision = "f32"
    return _mm_control(x, w, precision)


def sizes(cfg: dict) -> dict:
    dep, sa = cfg["deployment"], cfg["sa_config"]
    held = int(cfg["num_experts"])
    return dict(
        D=cfg["hidden_size"], F=cfg["moe_intermediate_size"],
        L=cfg["num_hidden_layers"], Hq=cfg["num_attention_heads"],
        Hkv=cfg["num_key_value_heads"], hd=cfg["head_dim"],
        Hi=int(sa["indexer_num_heads"]), di=int(sa["indexer_head_dim"]),
        topk=int(sa["topk"]), E=int(dep["routed_experts_total"]),
        held=held, first=int(dep["ep_rank"]) * held,
        k=int(cfg["num_experts_per_tok"]), V=cfg["vocab_size"],
        theta=float(cfg["rope_theta"]), eps=float(cfg["rms_norm_eps"]),
        sections=tuple(cfg["rope_scaling"]["mrope_section"]),
        dtype=jnp.dtype({"bfloat16": jnp.bfloat16,
                         "float32": jnp.float32}[cfg["torch_dtype"]]))


# ----------------------------------------------------------------------
# rotary tables
# ----------------------------------------------------------------------

def _angles(dim: int, theta: float, positions) -> np.ndarray:
    inv = 1.0 / (theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim))
    return np.asarray(positions, np.float64)[..., None] * inv


def rope_tables(cfg: dict, positions):
    """positions [S] (text) or [3, S] (time, height, width) -> (cos,
    sin) [S, hd / 2] of the main heads, pair i reading the component its
    section names, and (cos, sin) [S, di / 2] of the indexer at the
    first component."""
    s = sizes(cfg)
    pos = np.asarray(positions)
    if pos.ndim == 1:
        pos = np.stack([pos] * len(s["sections"]))
    full = _angles(s["hd"], s["theta"], pos)                # [3, S, hd/2]
    comp = np.repeat(np.arange(len(s["sections"])), s["sections"])
    main = full[comp, :, np.arange(s["hd"] // 2)].T         # [S, hd/2]
    idx = _angles(s["di"], s["theta"], pos[0])
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    return (f32(np.cos(main)), f32(np.sin(main)),
            f32(np.cos(idx)), f32(np.sin(idx)))


def _rope(x, cos, sin):
    """x [S, h, d] or [S, d]; cos/sin [S, d / 2]; dim i pairs with dim
    i + d / 2."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    if x.ndim == cos.ndim + 1:
        cos, sin = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


# ----------------------------------------------------------------------
# weights
# ----------------------------------------------------------------------

_EMBED_STD = 1.0        # module docstring, "Why the embedding"


def _near_one(key, n, dtype):
    return (1.0 + 0.1 * jax.random.normal(key, (n,), jnp.float32)
            ).astype(dtype)


def _expert(key, *, D, F, dtype):
    ks = jax.random.split(key, 3)
    return (_normal(ks[0], (D, F), D ** -0.5, dtype),
            _normal(ks[1], (D, F), D ** -0.5, dtype),
            _normal(ks[2], (F, D), F ** -0.5, dtype))


def _layer_weights(key, *, D, F, Hq, Hkv, hd, Hi, di, E, held, first,
                   dtype):
    ks = iter(jax.random.split(key, 16))
    w = {"ln_attn": _near_one(next(ks), D, dtype),
         "ln_mlp": _near_one(next(ks), D, dtype),
         "wq": _normal(next(ks), (D, Hq * hd), D ** -0.5, dtype),
         "wk": _normal(next(ks), (D, Hkv * hd), D ** -0.5, dtype),
         "wv": _normal(next(ks), (D, Hkv * hd), D ** -0.5, dtype),
         "wo": _normal(next(ks), (Hq * hd, D), (Hq * hd) ** -0.5, dtype),
         "q_norm": _near_one(next(ks), hd, dtype),
         "k_norm": _near_one(next(ks), hd, dtype),
         "w_qi": _normal(next(ks), (D, Hi * di), D ** -0.5, dtype),
         "w_ki": _normal(next(ks), (D, di), D ** -0.5, dtype),
         "w_w": _normal(next(ks), (D, Hi), D ** -0.5, dtype),
         "w_router": _normal(next(ks), (D, E), D ** -0.5, dtype)}
    ek = next(ks)
    eks = jax.vmap(lambda e: jax.random.fold_in(ek, e))(
        first + jnp.arange(held))
    g, u, d = jax.vmap(functools.partial(_expert, D=D, F=F, dtype=dtype))(
        eks)
    w.update(we_gate=g, we_up=u, we_down=d)   # [held, D, F] x 2, [held, F, D]
    return w


_WEIGHT_KEYS = ("D", "F", "Hq", "Hkv", "hd", "Hi", "di", "E", "held",
                "first", "dtype")


def layer_weights_fn(cfg: dict, out_sharding=None):
    """A jitted `key -> layer dict` for this configuration and share."""
    s = sizes(cfg)
    fn = functools.partial(_layer_weights,
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

def route(u, w_router, *, k, precision="f32"):
    """Softmax over ALL the experts the router has columns for, top-k,
    renormalised: (weights [S, k], expert numbers [S, k])."""
    probs = jax.nn.softmax(_mm(u, w_router, precision), axis=-1)
    w, idx = jax.lax.top_k(probs, k)
    return w / jnp.sum(w, axis=-1, keepdims=True), idx.astype(jnp.int32)


def _swiglu(u, g, up, d, precision):
    return _mm(jax.nn.silu(_mm(u, g, precision)) * _mm(u, up, precision),
               d, precision)


def routed_share(u, w, s, precision, with_held: bool = False):
    """What the held experts add for u [S, D]: every held expert over
    every token, times its routing weight (zero where it was not
    chosen). with_held: also which held experts each token chose, as a
    bit mask [S] uint32."""
    wts, idx = route(u, w["w_router"], k=s["k"], precision=precision)

    def one(acc, ew):
        e, g, up, d = ew
        gate = jnp.sum(jnp.where(idx == e, wts, 0.0), axis=-1)
        return acc + gate[..., None] * _swiglu(u, g, up, d, precision), None

    held_ids = s["first"] + jnp.arange(s["held"])
    acc, _ = jax.lax.scan(
        one, jnp.zeros_like(u),
        (held_ids, w["we_gate"], w["we_up"], w["we_down"]))
    if not with_held:
        return acc
    chose = jnp.any(idx[..., None] == held_ids, axis=-2)     # [S, held]
    bits = jnp.sum(jnp.where(
        chose, jnp.uint32(1) << (jnp.arange(s["held"], dtype=jnp.uint32)
                                 % 32), jnp.uint32(0)), axis=-1)
    return acc, bits


def index_scores(qi, ki, wi, s):
    """[rows, S] float32: I of the rows' queries against every key."""
    sc = jnp.einsum("qhd,kd->qhk", qi, ki, precision=HIGHEST)
    return jnp.sum(jax.nn.relu(sc) * wi[..., None], axis=1) \
        * (s["di"] ** -0.5) * (s["Hi"] ** -0.5)


def selection(scores, t0, s, broken=None):
    """scores [rows, S] of the queries at positions t0 .. t0 + rows - 1
    -> [rows, S] bool, their sets S_t. broken: a control's set of the
    same size that ignores the scores: "sel_last" the `topk` positions
    before and at t (a sliding window, whatever the indexer says)."""
    rows, S = scores.shape
    t = t0 + jnp.arange(rows)
    col = jnp.arange(S)[None, :]
    causal = col <= t[:, None]
    k = min(s["topk"], S)
    if broken == "sel_last":
        return causal & (col > t[:, None] - k)
    _, idx = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf), k)
    chosen = jnp.zeros((rows, S), bool).at[
        jnp.arange(rows)[:, None], idx].set(True)
    return chosen & causal


def _attn(u, w, rope, s, precision, block_rows, with_sets=False,
          with_masks=False):
    """Attn(u) for u [S, D] (with_sets: and [S, 2] uint32 naming each
    position's set; with_masks: the sets themselves, [S, S] bool)."""
    S = u.shape[0]
    Hq, Hkv, hd, Hi, di = s["Hq"], s["Hkv"], s["hd"], s["Hi"], s["di"]
    cos, sin, cos_i, sin_i = rope
    cached = _bf16 if precision == "bf16" else (lambda a: a)
    q = _mm(u, w["wq"], precision).reshape(S, Hq, hd)
    k = _mm(u, w["wk"], precision).reshape(S, Hkv, hd)
    v = cached(_mm(u, w["wv"], precision).reshape(S, Hkv, hd))
    q = cached(_rope(_rms(q, w["q_norm"], s["eps"]), cos, sin))
    k = cached(_rope(_rms(k, w["k_norm"], s["eps"]), cos, sin))
    qi = cached(_rope(_mm(u, w["w_qi"], precision).reshape(S, Hi, di),
                      cos_i, sin_i))
    ki = cached(_rope(_mm(u, w["w_ki"], precision), cos_i, sin_i))
    wi = _mm(u, w["w_w"], precision)
    g = Hq // Hkv
    nb = -(-S // block_rows)
    Sp = nb * block_rows
    pad = lambda a: jnp.pad(a, ((0, Sp - S),) + ((0, 0),) * (  # noqa
        a.ndim - 1)).reshape((nb, block_rows) + a.shape[1:])
    pos = jnp.arange(S, dtype=jnp.uint32)

    def block(xs):
        qb, qib, wib, t0 = xs
        chosen = selection(
            index_scores(qib, ki, wib, s), t0, s,
            broken=precision if precision in BROKEN_SELECTIONS else None)
        sc = jnp.einsum("qhgd,khd->hgqk", qb.reshape(-1, Hkv, g, hd), k,
                        precision=HIGHEST) * (hd ** -0.5)
        p = jax.nn.softmax(jnp.where(chosen[None, None], sc, -jnp.inf), -1)
        o = jnp.einsum("hgqk,khd->qhgd", p, v, precision=HIGHEST)
        # a signature of the set, for telling two passes' sets apart
        sig = jnp.stack([
            jnp.sum(jnp.where(chosen, pos, 0), -1, dtype=jnp.uint32),
            jnp.sum(jnp.where(chosen, pos * pos, 0), -1,
                    dtype=jnp.uint32)], -1)
        return o.reshape(-1, Hq * hd), sig, (chosen if with_masks else ())

    o, sig, masks = jax.lax.map(block, (pad(q), pad(qi), pad(wi),
                                        jnp.arange(nb) * block_rows))
    out = _mm(o.reshape(Sp, Hq * hd)[:S], w["wo"], precision)
    if with_masks:
        return out, masks.reshape(Sp, S)[:S]
    return (out, sig.reshape(Sp, 2)[:S]) if with_sets else out


def attention(cfg: dict, u, w, positions=None, precision="f32"):
    """(Attn(u) [S, D], the sets S_t [S, S] bool) of one layer's
    attention over u [S, D] at text positions or `positions` [3, S]:
    for tests at a small size."""
    s = sizes(cfg)
    rope = rope_tables(cfg, np.arange(u.shape[0]) if positions is None
                       else positions)
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    return _attn(u, w, rope, s, precision, min(128, u.shape[0]),
                 with_masks=True)


def _layer(x, w, rope, *, skey, precision, block_rows):
    """(the layer's output [S, D]; per position [S, 3] uint32: two
    sums that name its selected set, and the held experts it chose)."""
    s = dict(skey)
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    a, sig = _attn(_rms(x, w["ln_attn"], s["eps"]), w, rope, s, precision,
                   block_rows, with_sets=True)
    stream = _bf16 if precision == "bf16" else (lambda t: t)
    x = stream(x + a)
    routed, held = routed_share(_rms(x, w["ln_mlp"], s["eps"]), w, s,
                                precision, with_held=True)
    return stream(x + routed), jnp.concatenate([sig, held[:, None]], -1)


@functools.lru_cache(maxsize=None)
def _layer_fn(precision, skey, block_rows):
    return jax.jit(functools.partial(_layer, skey=skey,
                                     precision=precision,
                                     block_rows=block_rows))


def _skey(s):
    return tuple(sorted((k, v) for k, v in s.items() if k != "dtype"))


def layer_forward(cfg: dict, x, w, rope, precision="f32",
                  with_sets: bool = False, block_rows: int = 128):
    """One layer of this share over x [S, D] float32."""
    out = _layer_fn(precision, _skey(sizes(cfg)),
                    min(block_rows, x.shape[0]))(x, w, rope)
    return out if with_sets else out[0]


def all_logits(cfg: dict, seed: int, ids, precision: str = "f32",
               positions=None):
    """float32 logits [S, V] of every position of one short sequence
    (positions: [3, S] for multimodal positions; text by default)."""
    s = sizes(cfg)
    ids = np.asarray(ids, np.int32)
    rope = rope_tables(cfg, np.arange(len(ids)) if positions is None
                       else positions)
    hw = head_weights(cfg, seed)
    x = hw["embed"][ids].astype(jnp.float32)
    fn = layer_weights_fn(cfg)
    for li in range(s["L"]):
        x = layer_forward(cfg, x, fn(layer_key(seed, li)), rope, precision)
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
        rope = rope_tables(cfg, np.arange(S))
        hw = head_weights(cfg, seed)
        hidden = {p: [] for p in precisions}
        for q in sequences:
            ids = np.zeros((S,), np.int32)
            ids[:len(q)] = np.asarray(q, np.int32)
            x = f32(hw["embed"][ids])
            for p in precisions:
                hidden[p].append(x)
        # positions at which a lower precision's pass selected another
        # SET of positions, or of held experts, than the float32 pass,
        # in any layer
        flipped = {p: [np.zeros((S, 2), bool) for _ in sequences]
                   for p in precisions if p != "f32"}
        fn = layer_weights_fn(cfg)
        for li in range(s["L"]):
            w = fn(layer_key(seed, li))
            for j in range(len(sequences)):
                sigs = {}
                for p in precisions:
                    hidden[p][j], sig = layer_forward(
                        cfg, hidden[p][j], w, rope, p, with_sets=True)
                    sigs[p] = np.asarray(sig)
                for p, marks in flipped.items():
                    marks[j][:, 0] |= np.any(
                        sigs[p][:, :2] != sigs["f32"][:, :2], axis=-1)
                    marks[j][:, 1] |= sigs[p][:, 2] != sigs["f32"][:, 2]
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
            at = np.concatenate(at) if at else np.zeros((0, 2), bool)
            if at.size:
                for col, what in ((0, "selected-position set"),
                                  (1, "held-expert set")):
                    print(f"{what} differs from float32's ({p} pass of "
                          f"the reference, any layer) at "
                          f"{int(at[:, col].sum())} of {len(at)} served "
                          f"positions = {100.0 * at[:, col].mean():.2f} %",
                          flush=True)
        # for reading a run by hand: does the gap grow along a stream
        # (a cache or position fault) or not (rounding, a flipped set)
        for p in precisions:
            fifths = [np.array_split(g, 5) for g in out[p] if g.size >= 5]
            if fifths:
                print(f"gap by fifth of the served stream ({p}): " + " ".join(
                    f"{np.mean(np.concatenate([f[i] for f in fifths])):.5f}"
                    for i in range(5)), flush=True)
    return out
