"""End-to-end TP inference tests (reference: test/nvidia/test_tp_e2e.py +
test_e2e_inference.py — dist backends must produce the same generation
as the oracle backend)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from triton_dist_tpu.models import AutoLLM, Engine, tiny_qwen3

mesh = None
model = None


def setup_module(module):
    global mesh, model
    n = len(jax.devices())
    mesh = jax.make_mesh((n,), ("tp",))
    model = AutoLLM.from_config(tiny_qwen3(n), mesh)


def _prompt(B, S, vocab):
    rng = np.random.RandomState(3)
    return rng.randint(0, vocab, size=(B, S)).astype(np.int32)


def test_prefill_modes_match_oracle():
    n = mesh.shape["tp"]
    B, S = 1, 2 * n
    ids = jnp.asarray(_prompt(B, S, model.config.vocab_size))
    cache0 = model.make_cache(B, 4 * n)
    want, _ = jax.jit(lambda i, c: model.forward_tokens(i, c, "xla"))(
        ids, cache0)
    for mode in ("dist", "ar", "gemm_ar"):
        cache = model.make_cache(B, 4 * n)
        got, _ = jax.jit(
            lambda i, c, m=mode: model.forward_tokens(i, c, m))(ids, cache)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-2, rtol=2e-2,
                                   err_msg=f"mode {mode}")


def test_cache_decode_matches_full_forward():
    """Decode with KV cache == forward over the full sequence (the
    correctness contract behind the reference's engine decode loop)."""
    B, S = 1, 8
    ids = _prompt(B, S + 1, model.config.vocab_size)
    # full forward over S+1 tokens
    cache_full = model.make_cache(B, 32)
    logits_full, _ = jax.jit(
        lambda i, c: model.forward_tokens(i, c, "xla"))(
            jnp.asarray(ids), cache_full)
    # prefill S then decode 1
    cache = model.make_cache(B, 32)
    _, cache = jax.jit(lambda i, c: model.forward_tokens(i, c, "xla"))(
        jnp.asarray(ids[:, :S]), cache)
    logits_inc, _ = jax.jit(lambda i, c: model.forward_tokens(i, c, "xla"))(
        jnp.asarray(ids[:, S:]), cache)
    np.testing.assert_allclose(np.asarray(logits_inc),
                               np.asarray(logits_full), atol=2e-2,
                               rtol=2e-2)


def test_deleted_backend_is_an_unknown_backend():
    """The megakernel tick is gone (PR 34): its string ends at the
    unknown-backend refusal, which names every string the engine does
    serve, and that list is the one the module's docstring tabulates."""
    import re
    from triton_dist_tpu.models import engine
    assert "mega" not in engine.BACKENDS and len(engine.BACKENDS) == 7
    with pytest.raises(ValueError, match="unknown backend 'mega'") as e:
        Engine(model, max_seq=16, backend="mega")
    assert all(repr(b) in str(e.value) for b in engine.BACKENDS)
    documented = re.findall(r'^  "(\w+)" +<- ', engine.__doc__, re.M)
    assert tuple(documented) == engine.BACKENDS


@pytest.mark.parametrize("backend", ["ar", "gemm_ar"])
def test_engine_generates_same_tokens_as_oracle(backend):
    B, S, gen = 1, 8, 6
    ids = _prompt(B, S, model.config.vocab_size)
    oracle = Engine(model, max_seq=32, backend="xla")
    want = np.asarray(oracle.serve(ids, gen))
    eng = Engine(model, max_seq=32, backend=backend)
    got = np.asarray(eng.serve(ids, gen))
    assert got.shape == (B, gen)
    np.testing.assert_array_equal(got, want)


def test_sampled_decode_temp0_equals_greedy():
    """temperature=0 through the sampled scan == the greedy scan bit
    for bit (the differential the serving demo leans on)."""
    B, S, gen = 1, 8, 6
    ids = _prompt(B, S, model.config.vocab_size)
    greedy = Engine(model, max_seq=32, backend="xla")
    want = np.asarray(greedy.serve(ids, gen))
    for mode in ("top_k", "top_p"):
        eng = Engine(model, max_seq=32, backend="xla", sampling=mode,
                     temperature=0.0)
        got = np.asarray(eng.serve(ids, gen, seed=7))
        np.testing.assert_array_equal(got, want, err_msg=mode)


def test_sampled_decode_seed_behavior():
    """Same seed -> same generation; different seeds may differ, and at
    hot temperature the sampler must explore (not collapse to argmax).
    top_k=1 is greedy regardless of temperature."""
    B, S, gen = 2, 8, 8
    ids = _prompt(B, S, model.config.vocab_size)
    eng = Engine(model, max_seq=32, backend="xla", sampling="top_p",
                 temperature=5.0, top_p=0.98)
    a = np.asarray(eng.serve(ids, gen, seed=3))
    b = np.asarray(eng.serve(ids, gen, seed=3))
    np.testing.assert_array_equal(a, b)
    c = np.asarray(eng.serve(ids, gen, seed=4))
    assert not np.array_equal(a, c), "hot sampling ignored the seed"
    greedy = np.asarray(Engine(model, max_seq=32,
                               backend="xla").serve(ids, gen))
    k1 = Engine(model, max_seq=32, backend="xla", sampling="top_k",
                temperature=5.0, top_k=1)
    np.testing.assert_array_equal(np.asarray(k1.serve(ids, gen, seed=9)),
                                  greedy)


@pytest.mark.parametrize("backend", ["dist", "ar", "gemm_ar"])
def test_int8_model_through_comm_backends(backend):
    """int8-quantized weights stream through the comm-kernel GEMMs
    (int8 panels to VMEM, per-column dequant after the dot — VERDICT r3
    missing #1): generations must match the int8 flash path exactly."""
    B, S, gen = (2 if backend == "dist" else 1), 8, 6
    n = mesh.shape["tp"]
    if backend == "dist":
        B = max(B, n)  # row-sharded activations need B*S % n == 0
    ids = _prompt(B, S, model.config.vocab_size)
    mq = model.quantize_int8()
    want = np.asarray(Engine(mq, max_seq=32, backend="flash").serve(
        ids, gen))
    got = np.asarray(Engine(mq, max_seq=32, backend=backend).serve(
        ids, gen))
    np.testing.assert_array_equal(got, want, err_msg=backend)
