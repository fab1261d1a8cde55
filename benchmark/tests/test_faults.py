"""The rest of a run with the timed path broken underneath: the
harness's look for a chip is skipped (`require_chip=False`, tiny sizes
on the CPU), the program is broken from outside, and `correct` has to
come out false. The faults a served cell can have: a token altered
where it is produced, and (TP cells) the exchange between chips left
out. A sound run of the same cells passes (`python3 -m
benchmark.selfcheck` runs those)."""

import os
import time

import jax
import pytest

from benchmark import harness

_TD = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "testdata")


def _run(workload, faults):
    return harness.run_cell(workload, 2**31 + 21, 3.0, 0,
                            process_start=time.time(), require_chip=False,
                            root=_TD, faults=faults, drain_s=600.0)


def test_altered_token_is_not_correct():
    def alter(served):
        slots = served.srv.sched.slots
        inner = slots._run_chunk

        def run_chunk(chunk):
            toks = inner(chunk)        # [batch, chunk] on the device
            return (toks + 1) % served.model.config.vocab_size
        slots._run_chunk = run_chunk

    r = _run("tiny.selfcheck-closed", alter)
    assert r["correct"] is False
    assert r["compared"]["max_gap"]["value"] > \
        r["compared"]["max_gap"]["limit"]


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs 4 devices: "
                    "XLA_FLAGS=--xla_force_host_platform_device_count=4")
def test_exchange_left_out_is_not_correct(monkeypatch):
    import functools
    from jax.sharding import PartitionSpec as P
    from triton_dist_tpu.layers import tp_attn, tp_mlp

    def no_exchange(a, b, ctx):
        # every chip keeps its own partial product; the first chip's is
        # taken for the replicated result: the sum over chips is left out
        @functools.partial(jax.shard_map, mesh=ctx.mesh,
                           in_specs=(P(None, ctx.axis), P(ctx.axis, None)),
                           out_specs=P(None, None), check_vma=False)
        def f(a_loc, b_loc):
            return (a_loc @ b_loc).astype(a_loc.dtype)
        return f(a, b)

    monkeypatch.setattr(tp_attn, "gemm_allreduce", no_exchange)
    monkeypatch.setattr(tp_mlp, "gemm_allreduce", no_exchange)
    r = _run("tiny-tp4.selfcheck-closed", None)
    assert r["correct"] is False
