"""Fleet traffic plane (triton_dist_tpu/fleet/): prefix-aware routing,
elastic membership, SLO-aware shedding over N TokenServer replicas.

The contracts pinned here:
- A fleet of N=1 behind the router streams BITWISE what a plain
  TokenServer streams — the router relays, it never rewrites.
- Prefix-aware placement lands a repeated prompt on the warm replica:
  the fleet-wide prefill_skip_frac strictly beats round-robin's on the
  same workload, and the shadow-index bookkeeping (fed only by done
  messages on the wire) is what steered it.
- Session affinity breaks placement ties: one session pins to one
  replica even when no prefix matches.
- A replica killed MID-STREAM (chaos kill_replicas — abrupt socket
  death, no done message) resteers: the request is re-served on a
  survivor and the spliced stream is bitwise identical, with zero-leak
  pool invariants on every surviving replica.
- A chaos-slowed probe (slow_replicas) marks a replica unhealthy and
  routed-around; a clean probe readmits it. A joining replica is
  routable when add_replica returns (one probe period).
- Router shedding drops `batch` before `interactive` under
  saturation, and the per-class goodput/violations partition stays
  exact.
- The replica hot path stays compile-free under fleet traffic (churn
  guard), and the merged trace carries route→replica-admit flow
  arrows.

In-process replicas speak the REAL socket protocol (ephemeral ports,
serve_forever threads); same-config replicas share the process-wide
jitted programs so the fleet costs one compile. The multi-replica SLO
storm and the subprocess arm are marked slow (tier-1 budget —
tools/fleet_smoke.sh runs the full matrix).
"""

import logging
import os
import threading

import jax
import pytest

from triton_dist_tpu.fleet import (FleetRouter, InprocReplica,
                                   Membership, ShadowPrefixIndex,
                                   SubprocReplica, probe_stats)
from triton_dist_tpu.models import AutoLLM, Engine
from triton_dist_tpu.models.config import tiny_qwen3
from triton_dist_tpu.runtime.chaos import FaultInjector
from triton_dist_tpu.serving import (ByteTokenizer, TokenServer,
                                     request_stream)

mesh1 = None
_STATE = {}

PAGE, CHUNK = 8, 4


def setup_module(module):
    global mesh1
    mesh1 = jax.make_mesh((1,), ("tp",))


def _engine():
    """One shared 1-dev engine: every fleet in this module reuses the
    same jitted programs (same config), so N replicas cost ~zero extra
    compile bill."""
    if "eng" not in _STATE:
        cfg = tiny_qwen3(1)
        model = AutoLLM.from_config(cfg, mesh1)
        _STATE["eng"] = (cfg, Engine(model, max_seq=64, backend="xla"),
                         ByteTokenizer(cfg.vocab_size))
    return _STATE["eng"]


def _fleet(n, prefix="r", *, fault=None, policy="prefix", **router_kw):
    """n same-config in-process replicas + a router over them."""
    cfg, eng, tok = _engine()
    reps = [InprocReplica(f"{prefix}{i}", eng, tok, batch=2,
                          chunk=CHUNK, paged=True, page=PAGE)
            for i in range(n)]
    return FleetRouter(reps, tok, policy=policy, fault=fault,
                       **router_kw), reps


def _drain(router, prompt, **kw):
    out = router.run(prompt, **kw)
    assert out["done"].get("done") is True
    assert out["done"].get("error") is None, out["done"]
    return out


def _assert_replica_no_leak(replica):
    """The surviving-replica invariant after its streams retired:
    every page free XOR outstanding, no occupied slots, and nothing
    held once the tree lets go (test_resilience.py's chaos
    invariant)."""
    sched = replica.server.sched
    pool = sched.slots.prefix.pool
    assert pool.available + pool.outstanding == pool.num_pages
    assert not sched.slots.occupied
    sched.slots.prefix.tree.evict_until(10 ** 9)
    assert pool.pages_in_use == 0, "leaked page refs"
    assert pool.available == pool.num_pages - 1


# ----------------------------------------------------------------------
# shadow placement index (pure host logic — no model)
# ----------------------------------------------------------------------

def test_shadow_index_match_and_fold():
    idx = ShadowPrefixIndex(max_entries=4)
    idx.insert([1, 2, 3, 4])
    assert idx.match_len([1, 2, 3, 9]) == 3
    assert idx.match_len([5, 6]) == 0
    # an extension subsumes its prefix entry; a covered insert only
    # refreshes recency
    idx.insert([1, 2, 3, 4, 5, 6])
    assert len(idx) == 1
    idx.insert([1, 2])
    assert len(idx) == 1
    assert idx.match_len([1, 2, 3, 4, 5, 6, 7]) == 6
    # LRU cap evicts the oldest distinct conversation
    for s in ([7, 8], [9, 10], [11, 12], [13, 14]):
        idx.insert(s)
    assert len(idx) == 4
    assert idx.match_len([1, 2, 3]) == 0, "oldest entry must be gone"


# ----------------------------------------------------------------------
# N=1 differential: the router relays, it never rewrites
# ----------------------------------------------------------------------

def test_fleet_n1_router_equals_plain_server_bitwise():
    cfg, eng, tok = _engine()
    srv = TokenServer(eng, tok, batch=2, chunk=CHUNK, paged=True,
                      page=PAGE)
    th = threading.Thread(target=srv.serve_forever, daemon=True)
    th.start()
    want, want_done = [], None
    for msg in request_stream("127.0.0.1", srv.port, "n1 differential",
                              gen_len=12, seed=7):
        if msg.get("done"):
            want_done = msg
            break
        want.extend(msg["token_ids"])
    srv.stop()
    th.join(timeout=60)

    router, _ = _fleet(1, prefix="n1_")
    try:
        out = _drain(router, "n1 differential", gen_len=12, seed=7)
        assert out["token_ids"] == want
        done = out["done"]
        assert done["n_tokens"] == want_done["n_tokens"]
        assert done["replica"] == "n1_0"
        assert "resteered" not in done and done.get("error") is None
        st = router.stats()
        assert st["resteers"] == 0
        assert st["replicas"]["n1_0"]["healthy"] is True
    finally:
        router.shutdown()


# ----------------------------------------------------------------------
# prefix-aware placement vs round-robin
# ----------------------------------------------------------------------

def _shared_prefix_workload():
    # shared span of 29 bytes = 3 whole KV pages at PAGE=8; prompt +
    # gen stays under the replicas' max_seq=64
    system = "You are a helpful TPU fleet. "
    return [system + q for q in ("alpha?", "beta!", "gamma.",
                                 "delta;")]


def test_prefix_placement_beats_round_robin_skip_frac():
    """The cache-aware-placement win, measured: the same
    shared-system-prompt workload served twice — prefix policy routes
    every follow-up to the replica whose tree is warm, round-robin
    scatters them — and the FLEET-WIDE prefill_skip_frac must be
    strictly higher with the router on. Streams stay bitwise identical
    between the two policies (placement changes WHERE, never WHAT)."""
    prompts = _shared_prefix_workload()
    results = {}
    for policy, prefix in (("prefix", "pp"), ("rr", "pr")):
        router, _ = _fleet(2, prefix=prefix, policy=policy)
        try:
            results[policy] = {
                "streams": [
                    _drain(router, p, gen_len=8, seed=i)["token_ids"]
                    for i, p in enumerate(prompts)],
                "cache": router.fleet_cache_stats(),
                "stats": router.stats(),
            }
        finally:
            router.shutdown()
    assert results["prefix"]["streams"] == results["rr"]["streams"]
    skip_on = results["prefix"]["cache"]["prefill_skip_frac"]
    skip_rr = results["rr"]["cache"]["prefill_skip_frac"]
    assert skip_on > skip_rr, (
        f"prefix placement must beat round-robin: {skip_on} vs "
        f"{skip_rr}")
    st = results["prefix"]["stats"]
    assert st["router_prefix_hit_frac"] > 0.0
    # the repeated-prefix follow-ups were routed FOR the warm tree
    assert any(k.startswith("routed_requests{")
               and "reason=prefix" in k for k in st)
    # round-robin never consults the shadow
    assert results["rr"]["stats"]["router_prefix_hit_frac"] == 0.0


def test_session_affinity_tiebreak():
    """Distinct prompts share NO prefix (different first byte), so
    placement ties at 0 — the session pin must keep one conversation
    on one replica and be the recorded routing reason."""
    router, _ = _fleet(2, prefix="sa")
    try:
        homes = set()
        for i, word in enumerate(("alpha", "bravo", "charlie")):
            out = _drain(router, f"{word} asks something new {i}",
                         gen_len=6, seed=i, session="user-42")
            homes.add(out["done"]["replica"])
        assert len(homes) == 1, f"session bounced across {homes}"
        st = router.stats()
        assert st["sessions"] == 1
        assert any(k.startswith("routed_requests{")
                   and "reason=session" in k for k in st)
    finally:
        router.shutdown()


# ----------------------------------------------------------------------
# membership: kill mid-stream, slow probes, elastic join
# ----------------------------------------------------------------------

def test_replica_kill_midstream_resteers_bitwise():
    """chaos kill_replicas: the routed replica dies abruptly after the
    first relayed chunk (EOF, no done). The router must mark it dead,
    re-serve the request on the survivor, splice the streams bitwise,
    and the survivor must hold the zero-leak invariant."""
    ref_router, _ = _fleet(2, prefix="kr")
    try:
        want = _drain(ref_router, "kill me midstream", gen_len=16,
                      seed=3)["token_ids"]
    finally:
        ref_router.shutdown()

    fi = FaultInjector(kill_replicas=(0,))
    router, reps = _fleet(2, prefix="kx", fault=fi)
    try:
        out = _drain(router, "kill me midstream", gen_len=16, seed=3)
        assert out["token_ids"] == want, "resteer splice diverged"
        assert out["done"]["resteered"] == 1
        assert fi.injected["replica_kill"] == 1
        st = router.stats()
        assert st["resteers"] == 1
        healthy = [r for r, v in st["replicas"].items()
                   if v["healthy"]]
        assert len(healthy) == 1
        assert st[f"replica_healthy{{replica={healthy[0]}}}"] == 1.0
        dead = next(r for r in st["replicas"] if r not in healthy)
        assert st[f"replica_healthy{{replica={dead}}}"] == 0.0
        # the dead replica's shadow/pins were dropped with it
        assert dead not in st["shadow_entries"]
        assert any("reason=resteer" in k for k in st
                   if k.startswith("routed_requests{"))
        _assert_replica_no_leak(
            router.members.replicas[healthy[0]])
    finally:
        router.shutdown()


def test_membership_slow_probe_and_rejoin():
    """chaos slow_replicas: probe index 1 (the second add) times out →
    that replica is unhealthy and traffic routes around it; the next
    clean probe period readmits it."""
    fi = FaultInjector(slow_replicas=(1,))
    router, reps = _fleet(2, prefix="sp", fault=fi)
    try:
        assert router.members.healthy == {"sp0": True, "sp1": False}
        assert fi.injected["probe_slow"] == 1
        out = _drain(router, "routed around the slow one", gen_len=6)
        assert out["done"]["replica"] == "sp0"
        assert router.probe() == {"sp0": True, "sp1": True}
        assert router.members.probe_failures["sp1"] == 1
    finally:
        router.shutdown()


def test_elastic_join_admits_within_one_probe():
    """add_replica on a live fleet: the joiner answers its first probe
    and is routable the moment the call returns — round-robin must
    include it immediately."""
    cfg, eng, tok = _engine()
    router, _ = _fleet(1, prefix="ej", policy="rr")
    try:
        _drain(router, "before the join", gen_len=4)
        joiner = InprocReplica("ej_new", eng, tok, batch=2,
                               chunk=CHUNK, paged=True, page=PAGE)
        assert router.add_replica(joiner) is True
        assert router.members.healthy_rids() == ["ej0", "ej_new"]
        landed = {_drain(router, f"after the join {i}",
                         gen_len=4, seed=i)["done"]["replica"]
                  for i in range(2)}
        assert landed == {"ej0", "ej_new"}
    finally:
        router.shutdown()


# ----------------------------------------------------------------------
# SLO-aware shedding
# ----------------------------------------------------------------------

def test_router_shed_batch_before_interactive_partition_exact():
    """At saturation (shed_inflight=0 makes every request 'over'),
    batch and untagged shed with a structured error while interactive
    still serves — and the per-class goodput/violations partition on
    the ROUTER's telemetry stays exact. Latency-generous targets keep
    the partition a SCHEDULING signal (who finished), not CPU-CI
    latency noise."""
    router, _ = _fleet(1, prefix="sh", shed_inflight=0,
                       slo_classes=_STORM_CLASSES)
    try:
        shed = router.run("batch storm victim", gen_len=4,
                          slo="batch")
        assert "shed" in shed["done"]["error"]
        assert shed["token_ids"] == []
        ok = router.run("human waiting", gen_len=4, slo="interactive")
        assert ok["done"].get("error") is None
        assert len(ok["token_ids"]) == 4
        st = router.stats()
        assert st["shed_requests{slo=batch}"] == 1
        # exact partition, per class: every finished request is
        # goodput XOR violation (absent counter == never incremented)
        assert st.get("slo_goodput{slo=interactive}", 0) == 1
        assert st.get("slo_violations{slo=interactive}", 0) == 0
        assert st.get("slo_goodput{slo=batch}", 0) == 0
        assert st.get("slo_violations{slo=batch}", 0) == 1
    finally:
        router.shutdown()


# ----------------------------------------------------------------------
# churn guard + merged trace
# ----------------------------------------------------------------------

class _CompileCounter(logging.Handler):
    def __init__(self):
        super().__init__()
        self.names = []

    def emit(self, record):
        msg = record.getMessage()
        if msg.startswith("Compiling "):
            self.names.append(msg)


def test_fleet_replica_hot_path_no_recompile():
    """Zero new XLA programs per poll across the fleet: after one
    warming request, serving more traffic through BOTH replicas
    compiles nothing (the replicas share the process-wide jitted
    programs — the churn guard extended to the traffic plane)."""
    router, _ = _fleet(2, prefix="cg", policy="rr")

    def traffic(base_seed):
        # rr pins alpha->cg0, bravo->cg1 each pass; the second pass
        # exercises every steady-state shape INCLUDING the
        # prefix-cache skip path, so the guarded pass below is pure
        # steady state
        for i in range(2):
            for j, p in enumerate(("churn guard alpha",
                                   "churn guard bravo")):
                _drain(router, p, gen_len=6, seed=base_seed + 2 * i + j)
    try:
        traffic(0)
        counter = _CompileCounter()
        logger = logging.getLogger("jax._src.interpreters.pxla")
        logger.addHandler(counter)
        jax.config.update("jax_log_compiles", True)
        try:
            traffic(10)
        finally:
            jax.config.update("jax_log_compiles", False)
            logger.removeHandler(counter)
        assert not counter.names, (
            f"fleet hot path compiled: {counter.names}")
    finally:
        router.shutdown()


def test_merged_trace_flow_arrows_route_to_replica():
    """One merged timeline spans the fleet: the router's flow arrow
    starts on its own track ('route', phase s with the placement
    decision) and ends on the chosen replica's track; the replica's
    poll-loop spans ride in on offset tids with rebased timestamps."""
    cfg, eng, tok = _engine()
    reps = [InprocReplica(f"tr{i}", eng, tok, batch=2, chunk=CHUNK,
                          paged=True, page=PAGE, trace=True)
            for i in range(2)]
    router = FleetRouter(reps, tok, trace=True)
    try:
        _drain(router, "trace me across the fleet", gen_len=6)
        dump = router.export()
        flows = [e for e in dump["traceEvents"]
                 if e.get("cat") == "flow" and e["name"] == "route"]
        starts = [e for e in flows if e["ph"] == "s"]
        ends = [e for e in flows if e["ph"] == "f"]
        assert starts and ends
        assert starts[0]["args"]["replica"] in ("tr0", "tr1")
        assert {e["id"] for e in starts} >= {e["id"] for e in ends}
        assert ends[0]["tid"] != starts[0]["tid"], \
            "arrow must land on the replica's track"
        # replica-side poll spans merged in on offset tracks
        names = {e["args"]["name"]
                 for e in dump["traceEvents"] if e.get("ph") == "M"}
        assert any(n.startswith("tr0:") for n in names)
        assert any(e.get("tid", 0) >= 64 and e.get("ph") != "M"
                   for e in dump["traceEvents"]), \
            "replica-side spans missing from the merged trace"
    finally:
        router.shutdown()


# ----------------------------------------------------------------------
# slow arms: the SLO storm differential and the subprocess fleet
# ----------------------------------------------------------------------

# latency-generous classes: goodput == "completed cleanly", so the
# storm differential measures SCHEDULING (who finished), not CPU-CI
# latency noise; priorities still rank interactive above batch
_STORM_CLASSES = {
    "interactive": {"ttft_target_ms": 1e9, "itl_target_ms": 1e9,
                    "priority": 2.0},
    "batch": {"ttft_target_ms": 1e9, "itl_target_ms": 1e9,
              "priority": 0.0},
}


def _storm(router, *, n_interactive=4, n_batch=4, gen_len=16,
           batch_head_start_s=0.15):
    """Mixed-priority burst: batch requests land first (slots fill),
    then the interactive wave arrives on a saturated fleet."""
    results = {}

    def client(slo, i):
        try:
            out = router.run(f"storm {slo} {i} " + "x" * 16,
                             gen_len=gen_len, seed=i, slo=slo)
        except Exception as e:          # pragma: no cover - visibility
            out = {"token_ids": [], "done": {"error": repr(e)}}
        results[(slo, i)] = out

    batch_ts = [threading.Thread(target=client, args=("batch", i))
                for i in range(n_batch)]
    inter_ts = [threading.Thread(target=client,
                                 args=("interactive", i))
                for i in range(n_interactive)]
    for t in batch_ts:
        t.start()
    threading.Event().wait(batch_head_start_s)
    for t in inter_ts:
        t.start()
    for t in batch_ts + inter_ts:
        t.join(timeout=600)
    return results


@pytest.mark.slow
def test_slo_storm_interactive_goodput_router_vs_round_robin():
    """The tentpole differential: under the same mixed-priority storm
    on the same tight fleet (batch=1 x 2 replicas, no queue), the
    SLO-aware router (shed batch, busy-wait interactive) must beat the
    class-blind round-robin baseline on slo_goodput{slo=interactive} —
    STRICTLY — while each arm's per-class goodput+violations partition
    stays exact."""
    cfg, eng, tok = _engine()
    goodput = {}
    for arm, policy, kw in (
            ("router", "prefix", dict(shed_inflight=2,
                                      busy_retries=40)),
            ("rr", "rr", dict(busy_retries=0))):
        # max_queue=1, NOT 0: admission pulls from the waiting line,
        # so a zero-capacity queue refuses every submit and both arms
        # degenerate to goodput 0 — one queue slot keeps the fleet
        # tight (third concurrent request per replica goes busy) while
        # still serving anything at all
        reps = [InprocReplica(f"st_{arm}{i}", eng, tok, batch=1,
                              chunk=CHUNK, paged=True, page=PAGE,
                              max_queue=1,
                              slo_classes=_STORM_CLASSES)
                for i in range(2)]
        router = FleetRouter(reps, tok, policy=policy,
                             slo_classes=_STORM_CLASSES, **kw)
        try:
            _storm(router)
            st = router.stats()
            for slo in ("interactive", "batch"):
                good = st.get(f"slo_goodput{{slo={slo}}}", 0)
                viol = st.get(f"slo_violations{{slo={slo}}}", 0)
                assert good + viol == 4, (
                    f"{arm}/{slo}: partition broke "
                    f"({good}+{viol} != 4)")
            goodput[arm] = st.get("slo_goodput{slo=interactive}", 0)
        finally:
            router.shutdown()
    assert goodput["router"] == 4, (
        f"SLO-aware router dropped interactive work: {goodput}")
    assert goodput["router"] > goodput["rr"], (
        f"router must STRICTLY beat round-robin: {goodput}")


@pytest.mark.slow
def test_subprocess_replica_fleet_with_aot_warm_join():
    """The real-socket-protocol smoke arm: subprocess replicas behind
    the same router, a SIGKILL death discovered by probe, and an
    elastic joiner warm-starting from the shared TDTPU_AOT_CACHE (the
    join is a probe period, not a compile — PR 12's cache is what
    makes scale-up elastic)."""
    import tempfile
    cfg, eng, tok = _engine()
    with tempfile.TemporaryDirectory() as aot:
        env = dict(os.environ, JAX_PLATFORMS="cpu", TDTPU_AOT_CACHE=aot)
        rep0 = SubprocReplica("sub0", batch=2, paged=True, page=PAGE,
                              env=env)
        router = FleetRouter([rep0], tok)
        try:
            out = _drain(router, "hello subprocess fleet", gen_len=8)
            assert out["done"]["replica"] == "sub0"
            assert len(out["token_ids"]) == 8
            # the first boot seeded the shared AOT cache
            assert os.listdir(aot), "AOT cache not seeded"
            # elastic join: the second process warm-starts from it
            rep1 = SubprocReplica("sub1", batch=2, paged=True,
                                  page=PAGE, env=env)
            assert router.add_replica(rep1) is True
            assert router.members.healthy_rids() == ["sub0", "sub1"]
            # SIGKILL death: probes discover it, traffic re-routes
            rep0.kill()
            probes = router.probe()
            assert probes["sub0"] is False and probes["sub1"] is True
            out = _drain(router, "after the crash", gen_len=6)
            assert out["done"]["replica"] == "sub1"
        finally:
            router.shutdown()


def test_probe_stats_identity_handshake():
    """A probe that reaches a DIFFERENT replica than the roster says
    (port reuse after a crash) must read unhealthy, not as a healthy
    impostor."""
    cfg, eng, tok = _engine()
    real = InprocReplica("id_real", eng, tok, batch=2, chunk=CHUNK,
                         paged=True, page=PAGE)
    try:
        st = probe_stats(real.host, real.port)
        assert st["replica_id"] == "id_real"
        members = Membership()

        class _Impostor:
            rid = "id_expected"
            host, port = real.host, real.port
        assert members.add(_Impostor()) is False
        assert members.healthy == {"id_expected": False}
    finally:
        real.stop()
