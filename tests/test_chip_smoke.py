"""chip_smoke.py's control flow, rehearsed in-process on the CPU at
tiny_qwen3(1), and the compile-cache placement it reports.

The rehearsal runs the SAME code the chip run does (engine
differential, TokenServer + request_stream clients, pool invariant) —
only the model and the positions are smaller and the kernels are
interpreted. Its verdict line says "platform": "cpu", so it can never
be read as a pass on the chip."""

import importlib.util
import json
import os
import sys

import jax
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(_REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod      # its dataclass looks itself up
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture()
def cache_dir_restored():
    before = jax.config.jax_compilation_cache_dir
    yield before
    jax.config.update("jax_compilation_cache_dir", before)


def _json_lines(text):
    return [json.loads(ln) for ln in text.splitlines() if ln.strip()]


def test_rehearsal_runs_every_phase(chip_smoke, capsys,
                                    cache_dir_restored):
    assert chip_smoke.main(["--rehearse"]) == 0
    lines = _json_lines(capsys.readouterr().out)
    by_phase = {ln["phase"]: ln for ln in lines if "phase" in ln}
    assert set(by_phase) >= {"engine.init", "engine.prefill", "engine.decode",
                             "server", "total"}, sorted(by_phase)

    # the differential compared something and stayed inside its bound
    pre, dec = by_phase["engine.prefill"], by_phase["engine.decode"]
    assert pre["backend"] == "flash" and pre["ref_backend"] == "xla"
    assert 0 <= pre["max_err"] <= pre["tol"]
    assert dec["steps"] == chip_smoke.REHEARSAL.decode_steps
    assert 0 <= dec["worst_step"]["max_err"] <= dec["worst_step"]["tol"]

    # the server served every client through the prefix cache
    srv = by_phase["server"]
    assert srv["clients"] == chip_smoke.N_CLIENTS
    assert srv["stats"]["admissions"] == chip_smoke.N_CLIENTS
    assert srv["stats"]["hits"] >= 1
    assert srv["stats"]["prefill_tokens_skipped"] > 0

    # the verdict: exactly these keys, last, and it names the CPU
    assert lines[-1] == {"ok": True, "device": {
        "platform": "cpu", "kind": jax.devices()[0].device_kind,
        "count": 1}}
    assert by_phase["engine.init"]["compile_cache_dir"] == \
        jax.config.jax_compilation_cache_dir


def test_no_chip_no_verdict(chip_smoke, capsys):
    """Without --rehearse a host with no TPU gets a non-zero exit and
    no result line."""
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "no TPU" in out.err


def test_compile_cache_env_choice_is_left_alone(monkeypatch,
                                                cache_dir_restored):
    from triton_dist_tpu.runtime import place_compile_cache
    monkeypatch.setenv(_CACHE_ENV, "/chosen/by/the/operator")
    jax.config.update("jax_compilation_cache_dir", "/read/at/start-up")
    assert place_compile_cache() == "/read/at/start-up"
    assert jax.config.jax_compilation_cache_dir == "/read/at/start-up"


def test_compile_cache_default_is_fixed_in_checkout(monkeypatch,
                                                    cache_dir_restored):
    from triton_dist_tpu.runtime import place_compile_cache
    monkeypatch.delenv(_CACHE_ENV, raising=False)
    jax.config.update("jax_compilation_cache_dir", None)
    first = place_compile_cache()
    second = place_compile_cache()
    assert first == second == os.path.join(_REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first
