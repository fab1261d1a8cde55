"""Run a heavy interpreted case in a fresh subprocess with one retry.

The TPU-interpret substrate can (rarely, under host starvation) abort
the whole process; isolating the heaviest programs keeps that upstream
flake from taking the suite down — an assertion failure inside the
case still fails deterministically (no retry for real failures)."""

import os
import subprocess
import sys

import pytest

_TESTS = os.path.dirname(os.path.abspath(__file__))
_REPO = os.path.dirname(_TESTS)


def run_isolated(driver: str, case: str, tries: int = 3,
                 timeout: int = 1200):
    env = dict(os.environ)
    env.pop("PYTEST_CURRENT_TEST", None)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "PYTHONPATH": _REPO + os.pathsep + env.get("PYTHONPATH", ""),
    })
    shim = os.path.join(_REPO, "tools", "fakecpus.so")
    if os.path.exists(shim) and "fakecpus" not in env.get("LD_PRELOAD", ""):
        env["LD_PRELOAD"] = (shim + " " + env.get("LD_PRELOAD", "")).strip()
        env.setdefault("FAKE_NPROC", "32")
    last = None
    for _ in range(tries):
        p = subprocess.run(
            [sys.executable, os.path.join(_TESTS, driver), case],
            env=env, capture_output=True, text=True, timeout=timeout)
        if p.returncode == 0 and "CASE_OK" in p.stdout:
            return
        last = p
        if "AssertionError" in (p.stderr or ""):
            break   # a real differential failure — do not retry
    pytest.fail(f"{driver}:{case} rc={last.returncode}\n"
                f"{last.stdout[-2000:]}\n{last.stderr[-4000:]}")
