"""Multi-host launch via the framework's env convention (the torchrun
analog, runtime/bootstrap.py::_maybe_init_multihost): this script
spawns TWO OS processes that join one JAX coordination service and run
a collective over the global mesh. On a real pod slice, run one process
per host with the same env vars (or TDTPU_MULTIHOST=1 on Cloud TPU)."""

import os
import socket
import subprocess
import sys
import textwrap

_CHILD = textwrap.dedent("""
    import os, sys
    sys.path.insert(0, os.environ["TDTPU_REPO"])
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from triton_dist_tpu.runtime import initialize_distributed

    ctx = initialize_distributed({"dcn": 2, "tp": 4})
    me = jax.process_index()
    x = jax.make_array_from_callback(
        (16, 4), NamedSharding(ctx.mesh, P(("dcn", "tp"), None)),
        lambda idx: np.full((2, 4), float(idx[0].start), np.float32))
    total = float(jax.jit(jnp.sum)(x))
    print(f"process {me}: {jax.process_count()} processes, "
          f"{len(jax.devices())} global devices, sum={total}")
""")


def main():
    s = socket.socket()
    s.bind(("localhost", 0))
    port = s.getsockname()[1]
    s.close()
    procs = []
    for pid in range(2):
        env = dict(os.environ)
        env.update({
            "TDTPU_REPO": os.path.dirname(
                os.path.dirname(os.path.abspath(__file__))),
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
            "JAX_COORDINATOR_ADDRESS": f"localhost:{port}",
            "JAX_NUM_PROCESSES": "2",
            "JAX_PROCESS_ID": str(pid),
        })
        procs.append(subprocess.Popen([sys.executable, "-c", _CHILD],
                                      env=env))
    rc = [p.wait(timeout=600) for p in procs]
    assert rc == [0, 0], rc
    print("multihost OK")


if __name__ == "__main__":
    main()
