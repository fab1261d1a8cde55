"""The benchmark's own tests run on the CPU: `python3 -m pytest
benchmark/tests -q -p no:cacheprovider` from the checkout's root (with
`XLA_FLAGS=--xla_force_host_platform_device_count=4` for the TP case;
without it that case skips)."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("JAX_CPU_ENABLE_ASYNC_DISPATCH", "false")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
