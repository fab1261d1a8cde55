"""Megakernel tests: the single-kernel decode layer vs a jnp oracle,
plus builder scoreboard-order validation (reference analogs: the
mega_triton_kernel model tests and its dependency checking)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from triton_dist_tpu.mega import (MegaDecodeLayer, MegaKernelBuilder,
                                  mega_decode_layer_ref)


def _mk_layer(B=4, D=256, Hq=4, Hkv=2, hd=64, F=512, T=256, seed=0):
    rng = np.random.RandomState(seed)
    sc = 0.3 / np.sqrt(D)
    half = hd // 2
    w = {
        "w_ln1": jnp.asarray(1 + 0.1 * rng.randn(1, D), jnp.float32),
        "w_qkv": jnp.asarray(rng.randn(D, (Hq + 2 * Hkv) * hd) * sc,
                             jnp.float32),
        "q_norm": jnp.asarray(1 + 0.1 * rng.randn(1, hd), jnp.float32),
        "k_norm": jnp.asarray(1 + 0.1 * rng.randn(1, hd), jnp.float32),
        "w_o": jnp.asarray(rng.randn(Hq * hd, D) * sc, jnp.float32),
        "w_ln2": jnp.asarray(1 + 0.1 * rng.randn(1, D), jnp.float32),
        "w_gu": jnp.asarray(rng.randn(D, 2 * F) * sc, jnp.float32),
        "w_d": jnp.asarray(rng.randn(F, D) * (0.3 / np.sqrt(F)),
                           jnp.float32),
    }
    x = jnp.asarray(rng.randn(B, D), jnp.float32) * 0.3
    ck = jnp.asarray(rng.randn(Hkv, B, T, hd), jnp.bfloat16) * 0.3
    cv = jnp.asarray(rng.randn(Hkv, B, T, hd), jnp.bfloat16) * 0.3
    return x, w, ck, cv


@pytest.mark.parametrize("pos", [0, 7, 130])
def test_mega_decode_layer_vs_oracle(pos):
    B, D, Hq, Hkv, hd, F, T = 4, 256, 4, 2, 64, 512, 256
    x, w, ck, cv = _mk_layer(B, D, Hq, Hkv, hd, F, T, seed=pos)
    inv = 1.0 / (1e6 ** (np.arange(0, hd, 2) / hd))
    w = dict(w)
    w["cos_row"] = jnp.asarray(np.cos(pos * inv)[None], jnp.float32)
    w["sin_row"] = jnp.asarray(np.sin(pos * inv)[None], jnp.float32)

    layer = MegaDecodeLayer(d_model=D, n_heads=Hq, n_kv_heads=Hkv,
                            head_dim=hd, ffn=F, T=T)
    with jax.default_matmul_precision("highest"):
        y, ck2, cv2 = jax.jit(
            lambda *a: layer(*a))(x, jnp.int32(pos), w, ck, cv)
        ry, rck, rcv = mega_decode_layer_ref(
            x, pos, w, ck, cv, n_heads=Hq, n_kv_heads=Hkv, head_dim=hd)
    # bf16 weights inside the kernel vs f32 oracle: loose-ish tolerance
    np.testing.assert_allclose(np.asarray(y), np.asarray(ry), atol=0.05,
                               rtol=0.05)
    np.testing.assert_allclose(
        np.asarray(ck2, dtype=np.float32),
        np.asarray(rck, dtype=np.float32), atol=1e-2, rtol=1e-2)
    np.testing.assert_allclose(
        np.asarray(cv2, dtype=np.float32),
        np.asarray(rcv, dtype=np.float32), atol=1e-2, rtol=1e-2)


def test_builder_rejects_misordered_program():
    b = MegaKernelBuilder()
    b.inputs("x", "y")
    b.buffer("tmp", (4, 4), jnp.float32)
    with pytest.raises(ValueError, match="before any task wrote"):
        b.add_task("use_tmp", lambda env: None, reads=("tmp",),
                   writes=("y",))
    # undeclared names are rejected outright
    with pytest.raises(ValueError, match="undeclared"):
        b.add_task("typo", lambda env: None, reads=("x",),
                   writes=("tmpp",))
    # correct order passes
    b.add_task("make_tmp", lambda env: None, reads=("x",),
               writes=("tmp",))
    b.add_task("use_tmp", lambda env: None, reads=("tmp",),
               writes=("y",))
    assert [t.name for t in b.tasks] == ["make_tmp", "use_tmp"]


def test_mega_engine_backend_matches_flash():
    """Greedy decode through backend='mega' (one megakernel per layer)
    must match the flash backend's tokens on a bf16 model — the e2e
    differential the reference's megakernel example runs against its
    torch engine (mega_triton_kernel/models/model_builder.py:86)."""
    from triton_dist_tpu.models import AutoLLM, Engine
    from triton_dist_tpu.models.config import tiny_qwen3
    from jax.sharding import Mesh

    mesh1 = Mesh(np.asarray(jax.devices()[:1]), ("tp",))
    cfg = tiny_qwen3(1, hidden_size=128, intermediate_size=256,
                     num_heads=2, num_kv_heads=1, head_dim=64,
                     dtype="bfloat16", max_position_embeddings=256)
    model = AutoLLM.from_config(cfg, mesh1)
    ids = np.random.RandomState(0).randint(
        0, cfg.vocab_size, size=(2, 8)).astype(np.int32)
    toks_f = np.asarray(
        Engine(model, max_seq=64, backend="flash").serve(ids, 5))
    toks_m = np.asarray(
        Engine(model, max_seq=64, backend="mega").serve(ids, 5))
    np.testing.assert_array_equal(toks_f, toks_m)


# tier-1 budget: the tp=4 megakernel e2e cases are among the suite's
# heaviest (ISSUE 1 satellite)
@pytest.mark.slow
def test_mega_engine_tp_decode_matches_dist():
    """backend='mega' at TP=4 (r5): one megakernel per layer per chip
    with in-kernel AR tasks — greedy tokens must match the per-op
    'dist' backend on the same bf16 model (the reference's flagship
    e2e, model_builder.py:86 TP=8 Qwen3)."""
    from triton_dist_tpu.models import AutoLLM, Engine
    from triton_dist_tpu.models.config import tiny_qwen3

    if len(jax.devices()) < 4:
        pytest.skip("needs >= 4 devices")
    mesh = jax.make_mesh((4,), ("tp",))
    # local widths (D, I/n, Hq*hd/n) must be 128-multiples
    cfg = tiny_qwen3(4, hidden_size=128, intermediate_size=512,
                     num_heads=8, num_kv_heads=4, head_dim=64,
                     dtype="bfloat16", max_position_embeddings=256)
    model = AutoLLM.from_config(cfg, mesh)
    ids = np.random.RandomState(0).randint(
        0, cfg.vocab_size, size=(4, 8)).astype(np.int32)  # B % tp == 0
    gen = 5
    toks_d = np.asarray(
        Engine(model, max_seq=64, backend="dist").serve(ids, gen))
    toks_m = np.asarray(
        Engine(model, max_seq=64, backend="mega").serve(ids, gen))
    # The two backends are numerically different-but-correct (bf16
    # dots, different reduction orders), so CHAINED greedy equality is
    # not a sound invariant — one near-tie flips every later token of
    # the row, and the old >= 0.75 agreement bound let real numeric
    # drift hide behind "near-tie divergence". Compare LOGITS instead
    # (ADVICE item): teacher-force each backend's OWN token stream
    # through the xla-oracle prefill and require every chosen token's
    # oracle logit to sit within a bf16-scale margin of the oracle
    # argmax. Drift in either backend shows up directly as a large
    # margin; a genuine near-tie stays within it.
    tol = 0.05
    oracle = Engine(model, max_seq=64, backend="xla")

    def near_argmax(toks):
        full = np.concatenate([ids, toks], 1)
        S = ids.shape[1]
        for i in range(gen):
            # oracle distribution for generated token i = prefill
            # logits of the teacher-forced prefix ending right before
            step = np.asarray(oracle.prefill(full[:, :S + i])[0])
            chosen = np.take_along_axis(
                step, toks[:, i][:, None], axis=1)[:, 0]
            gap = step.max(-1) - chosen
            assert (gap <= tol).all(), (i, gap, toks)

    near_argmax(toks_d)
    near_argmax(toks_m)


def test_mega_engine_rejects_indivisible_tp():
    from triton_dist_tpu.models import AutoLLM, Engine
    from triton_dist_tpu.models.config import tiny_qwen3

    n = len(jax.devices())
    if n < 2:
        pytest.skip("needs a multi-device mesh")
    mesh = jax.make_mesh((n,), ("tp",))
    # heads NOT divisible by the mesh: num_heads = n + 1
    model = AutoLLM.from_config(
        tiny_qwen3(n, num_heads=n + 1, num_kv_heads=n + 1), mesh)
    with pytest.raises(ValueError, match="divisible"):
        Engine(model, backend="mega")


@pytest.mark.slow
def test_mega_decode_layer_tp_vs_oracle():
    """TP megakernel (r5, the reference's FLAGSHIP composition —
    model_builder.py:86 TP=8 Qwen3 with allreduce tasks inside the
    kernel): the layer stays ONE kernel per chip with the two
    cross-chip reductions (o-proj / down-proj partials) as in-kernel
    one-shot AR sections. tp=4 over head/ffn shards vs the full-weight
    oracle."""
    import functools
    from jax.sharding import PartitionSpec as P

    if len(jax.devices()) < 4:
        pytest.skip("needs >= 4 devices")
    n = 4
    mesh4 = jax.make_mesh((n,), ("tp",))
    B, D, Hq, Hkv, hd, F, T = 4, 256, 8, 4, 64, 512, 256
    pos = 37
    x, w, ck, cv = _mk_layer(B, D, Hq, Hkv, hd, F, T, seed=3)
    inv = 1.0 / (1e6 ** (np.arange(0, hd, 2) / hd))
    w = dict(w)
    w["cos_row"] = jnp.asarray(np.cos(pos * inv)[None], jnp.float32)
    w["sin_row"] = jnp.asarray(np.sin(pos * inv)[None], jnp.float32)

    with jax.default_matmul_precision("highest"):
        ry, rck, rcv = mega_decode_layer_ref(
            x, pos, w, ck, cv, n_heads=Hq, n_kv_heads=Hkv, head_dim=hd)

    # rearrange packed weights so a contiguous column split gives each
    # rank its own [q_loc | k_loc | v_loc] / [gate_loc | up_loc] block
    Hq_l, Hkv_l, F_l = Hq // n, Hkv // n, F // n
    wq = np.asarray(w["w_qkv"])
    qs, ks, vs = (wq[:, :Hq * hd], wq[:, Hq * hd:(Hq + Hkv) * hd],
                  wq[:, (Hq + Hkv) * hd:])
    blocks = []
    for r in range(n):
        blocks += [qs[:, r * Hq_l * hd:(r + 1) * Hq_l * hd],
                   ks[:, r * Hkv_l * hd:(r + 1) * Hkv_l * hd],
                   vs[:, r * Hkv_l * hd:(r + 1) * Hkv_l * hd]]
    wq_tp = jnp.asarray(np.concatenate(blocks, 1))
    wgu = np.asarray(w["w_gu"])
    g_, u_ = wgu[:, :F], wgu[:, F:]
    gu_blocks = []
    for r in range(n):
        gu_blocks += [g_[:, r * F_l:(r + 1) * F_l],
                      u_[:, r * F_l:(r + 1) * F_l]]
    wgu_tp = jnp.asarray(np.concatenate(gu_blocks, 1))
    w_tp = dict(w, w_qkv=wq_tp, w_gu=wgu_tp)

    layer = MegaDecodeLayer(d_model=D, n_heads=Hq_l, n_kv_heads=Hkv_l,
                            head_dim=hd, ffn=F_l, T=T, tp=n,
                            block_n=128)
    rep2 = P(None, None)
    w_specs = {"w_ln1": rep2, "w_qkv": P(None, "tp"), "q_norm": rep2,
               "k_norm": rep2, "w_o": P("tp", None), "w_ln2": rep2,
               "w_gu": P(None, "tp"), "w_d": P("tp", None),
               "cos_row": rep2, "sin_row": rep2}
    cspec = P("tp", None, None, None)

    @functools.partial(
        jax.shard_map, mesh=mesh4,
        in_specs=(rep2, w_specs, cspec, cspec),
        out_specs=(rep2, cspec, cspec), check_vma=False)
    def run(x_, wd, ck_, cv_):
        return layer(x_, jnp.int32(pos), wd, ck_, cv_)

    with jax.default_matmul_precision("highest"):
        y, ck2, cv2 = jax.jit(run)(x, w_tp, ck, cv)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ry),
                               atol=0.05, rtol=0.05)
    np.testing.assert_allclose(np.asarray(ck2, dtype=np.float32),
                               np.asarray(rck, dtype=np.float32),
                               atol=1e-2, rtol=1e-2)
    np.testing.assert_allclose(np.asarray(cv2, dtype=np.float32),
                               np.asarray(rcv, dtype=np.float32),
                               atol=1e-2, rtol=1e-2)
