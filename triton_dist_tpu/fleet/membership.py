"""Elastic fleet membership: replica handles + health over the wire.

A replica is just a TokenServer reachable at (host, port); membership
is the router's belief about which of them can take traffic. There is
no side channel: a HEALTH PROBE is the existing ``{"op": "stats"}``
protocol request (serving.py answers it with one deep-snapshot reply
and no slot consumed), and the ``replica_id`` echo in that snapshot
doubles as the identity handshake — a probe that reaches the wrong
process (port reuse after a crash) reads as unhealthy, not as a
healthy impostor.

Two replica shapes, one probe surface:

- InprocReplica — a TokenServer on its own ephemeral port with
  serve_forever in a daemon thread. The deterministic test arm: N
  same-config replicas share the process-wide jitted engine programs,
  so a fleet costs one compile. kill() is an ABRUPT death (client
  sockets slammed, no graceful done fan-out) so failover paths see
  what a crashed replica actually looks like: EOF mid-stream.
- SubprocReplica — ``python -m triton_dist_tpu.fleet.membership`` in a
  child process over the real socket protocol. The slow/smoke arm:
  true process isolation, a kill() is a SIGKILL, and a joiner
  warm-starts from the shared AOT program cache when TDTPU_AOT_CACHE
  is set (PR 12) — which is what makes elastic scale-up admit within
  one probe period instead of one compile.

Membership.add() probes synchronously, so a joining replica is
routable the moment add() returns — "admits within one probe period"
is the call contract, not an eventual-consistency hope. A probe
consults FaultInjector.router_probe first (runtime/chaos.py
``slow_replicas``): a chaos-slowed probe behaves as timed out and the
replica is routed around until a clean probe readmits it.
"""
from __future__ import annotations

import json
import socket
import subprocess
import sys
import threading
import time
from collections import OrderedDict
from typing import Callable, Dict, List, Optional


def probe_stats(host: str, port: int, *,
                timeout: float = 2.0) -> dict:
    """One health probe: the in-protocol stats fetch. Returns the
    stats snapshot; raises OSError/ValueError on anything less than a
    well-formed reply within the timeout (refusals, garbage, EOF)."""
    with socket.create_connection((host, port),
                                  timeout=timeout) as s:
        s.settimeout(timeout)
        with s.makefile("rw") as f:
            f.write(json.dumps({"op": "stats"}) + "\n")
            f.flush()
            line = f.readline()
    if not line:
        raise ValueError("probe: connection closed without a reply")
    msg = json.loads(line)
    if not msg.get("done") or not isinstance(msg.get("stats"), dict):
        raise ValueError(f"probe: malformed stats reply "
                         f"{sorted(msg)!r}")
    return msg["stats"]


class InprocReplica:
    """One TokenServer replica inside this process (deterministic test
    arm). Construction binds the port and starts serve_forever in a
    daemon thread; the handle exposes the (rid, host, port) triple the
    router and membership speak to — over the REAL socket protocol,
    same as a remote replica."""

    def __init__(self, rid: str, engine, tokenizer, *,
                 batch: int, **server_kwargs):
        from triton_dist_tpu.serving import TokenServer
        self.rid = str(rid)
        self.server = TokenServer(engine, tokenizer, batch=batch,
                                  replica_id=self.rid,
                                  **server_kwargs)
        self.host, self.port = self.server.host, self.server.port
        self.thread = threading.Thread(
            target=self.server.serve_forever, daemon=True,
            name=f"replica-{self.rid}")
        self.thread.start()

    def stats(self) -> dict:
        return self.server.stats()

    def kill(self, *, join_timeout_s: float = 30.0) -> None:
        """Abrupt death: every live client socket is slammed (their
        streams end at EOF with NO done message — exactly what a
        crashed process looks like from the wire) and the serve loop
        stops. The listener closes via serve_forever's own teardown
        (which the loop reaches within one idle sleep and which also
        ends its acceptor thread), so probes are refused from then on."""
        srv = self.server
        srv._stop.set()
        for cs in list(srv._conns.values()):
            cs.dead = True
            for slam in (lambda: cs.conn.shutdown(socket.SHUT_RDWR),
                         cs.conn.close):
                try:
                    slam()
                except OSError:
                    pass
        self.thread.join(timeout=join_timeout_s)

    def stop(self, *, join_timeout_s: float = 30.0) -> None:
        """Graceful shutdown (drains via the serve loop's teardown)."""
        self.server.stop()
        self.thread.join(timeout=join_timeout_s)


class SubprocReplica:
    """One TokenServer replica in a child process (the slow/smoke
    arm): real process isolation over the real socket protocol. The
    child prints ``PORT=<n>`` once its listener is bound; kill() is a
    SIGKILL — no cleanup, the probe path must discover the death."""

    def __init__(self, rid: str, *, batch: int = 2, chunk: int = 4,
                 paged: bool = True, page: int = 8,
                 num_pages: Optional[int] = None, max_seq: int = 64,
                 env: Optional[dict] = None,
                 startup_timeout_s: float = 300.0):
        self.rid = str(rid)
        argv = [sys.executable, "-m",
                "triton_dist_tpu.fleet.membership",
                "--replica-id", self.rid, "--batch", str(batch),
                "--chunk", str(chunk), "--page", str(page),
                "--max-seq", str(max_seq)]
        if paged:
            argv.append("--paged")
        if num_pages is not None:
            argv += ["--num-pages", str(num_pages)]
        self.proc = subprocess.Popen(
            argv, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=subprocess.DEVNULL, text=True, env=env)
        self.host = "127.0.0.1"
        self.port = self._await_port(startup_timeout_s)

    def _await_port(self, timeout_s: float) -> int:
        # the child prints exactly one PORT= line after binding; model
        # build/compile happens first, so give it the smoke budget
        timer = threading.Timer(timeout_s, self.proc.kill)
        timer.start()
        try:
            for line in self.proc.stdout:
                if line.startswith("PORT="):
                    return int(line.strip().split("=", 1)[1])
        finally:
            timer.cancel()
        raise RuntimeError(
            f"replica {self.rid}: child exited "
            f"(rc={self.proc.poll()}) before announcing its port")

    def kill(self) -> None:
        self.proc.kill()
        self.proc.wait(timeout=30)

    def stop(self) -> None:
        """Graceful: closing stdin is the shutdown signal the child's
        watcher thread waits on."""
        try:
            self.proc.stdin.close()
        except OSError:
            pass
        try:
            self.proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            self.kill()


class Membership:
    """The fleet roster: replica handles + per-replica health belief.
    Health transitions drive the ``replica_healthy{replica=}`` gauge
    (when a registry is attached) and the on_death/on_join callbacks
    the router uses to drop a dead replica's shadow index and session
    pins."""

    def __init__(self, *, probe_timeout_s: float = 2.0, fault=None,
                 registry=None):
        self.probe_timeout_s = float(probe_timeout_s)
        self.fault = fault
        self.registry = registry
        self.replicas: "OrderedDict[str, object]" = OrderedDict()
        self.healthy: Dict[str, bool] = {}
        self.last_stats: Dict[str, dict] = {}
        self.probe_failures: Dict[str, int] = {}
        self.on_death: Optional[Callable[[str], None]] = None
        self.on_join: Optional[Callable[[str], None]] = None
        # on_probe(rid, ok, latency_s) after EVERY probe verdict — the
        # circuit breaker's EMA feed (fleet/ha.py). on_change(rid, ok)
        # only on health TRANSITIONS — the HA journal's membership
        # feed, so a standby can rebuild the roster from edges alone.
        self.on_probe: Optional[
            Callable[[str, bool, float], None]] = None
        self.on_change: Optional[Callable[[str, bool], None]] = None

    def add(self, replica) -> bool:
        """Register + synchronously probe: a joiner that answers its
        first probe is routable when this returns (one probe period —
        the elastic-join contract). Returns the health verdict."""
        rid = replica.rid
        if rid in self.replicas:
            raise ValueError(f"duplicate replica id {rid!r}")
        self.replicas[rid] = replica
        self.healthy[rid] = False
        self.probe_failures[rid] = 0
        return self.probe(rid)

    def remove(self, rid: str) -> None:
        self.replicas.pop(rid, None)
        self.healthy.pop(rid, None)
        self.last_stats.pop(rid, None)
        self.probe_failures.pop(rid, None)

    def healthy_rids(self) -> List[str]:
        """Routable replicas, in registration order (the deterministic
        tiebreak every placement decision bottoms out on)."""
        return [rid for rid in self.replicas if self.healthy[rid]]

    def mark_dead(self, rid: str) -> None:
        """Out-of-band death verdict (the router saw a mid-stream EOF
        — faster than waiting for the next probe period)."""
        if rid in self.healthy:
            self._set_health(rid, False)

    def probe(self, rid: str) -> bool:
        """One health probe of one replica. Chaos first
        (FaultInjector.router_probe — a slowed replica behaves as a
        probe timeout), then the wire: a stats reply whose replica_id
        echo matches is healthy; anything else is not."""
        replica = self.replicas[rid]
        ok = False
        t0 = time.monotonic()
        if self.fault is not None and self.fault.router_probe(rid):
            # a chaos-slowed probe is a TIMEOUT, and it must look like
            # one to the breaker's latency EMA too — report the full
            # timeout budget, not the instant chaos verdict
            latency_s = self.probe_timeout_s
        else:
            try:
                st = probe_stats(replica.host, replica.port,
                                 timeout=self.probe_timeout_s)
                # EXACT echo required: a bare TokenServer (no
                # replica_id) on a reused port must read as an
                # impostor, not as healthy — every fleet replica
                # shape sets replica_id at construction
                if st.get("replica_id") == rid:
                    self.last_stats[rid] = st
                    ok = True
            except (OSError, ValueError):
                ok = False
            latency_s = time.monotonic() - t0
        if not ok:
            self.probe_failures[rid] += 1
        if self.on_probe is not None:
            self.on_probe(rid, ok, latency_s)
        self._set_health(rid, ok)
        return ok

    def probe_all(self) -> Dict[str, bool]:
        return {rid: self.probe(rid) for rid in list(self.replicas)}

    def _set_health(self, rid: str, ok: bool) -> None:
        was = self.healthy.get(rid)
        self.healthy[rid] = ok
        if self.registry is not None:
            self.registry.gauge(
                "replica_healthy", "1 = the replica answers probes "
                "and takes traffic", labels={"replica": rid}).set(
                1.0 if ok else 0.0)
        if was is not False and not ok and self.on_death is not None:
            self.on_death(rid)
        if was is False and ok and self.on_join is not None:
            self.on_join(rid)
        if was is not ok and self.on_change is not None:
            self.on_change(rid, ok)


def _main(argv: Optional[List[str]] = None) -> int:
    """Subprocess replica entry point (SubprocReplica's child): build
    the tiny reference model on a 1-device mesh, serve on an ephemeral
    port, announce it as PORT=<n>, and shut down when stdin closes (a
    dead parent cannot leak children)."""
    import argparse
    p = argparse.ArgumentParser()
    p.add_argument("--replica-id", required=True)
    p.add_argument("--batch", type=int, default=2)
    p.add_argument("--chunk", type=int, default=4)
    p.add_argument("--page", type=int, default=8)
    p.add_argument("--num-pages", type=int, default=None)
    p.add_argument("--max-seq", type=int, default=64)
    p.add_argument("--paged", action="store_true")
    args = p.parse_args(argv)

    import jax
    from triton_dist_tpu.models import AutoLLM, Engine
    from triton_dist_tpu.models.config import tiny_qwen3
    from triton_dist_tpu.serving import ByteTokenizer, TokenServer

    cfg = tiny_qwen3(1)
    mesh = jax.make_mesh((1,), ("tp",))
    model = AutoLLM.from_config(cfg, mesh)
    eng = Engine(model, max_seq=args.max_seq, backend="xla")
    tok = ByteTokenizer(cfg.vocab_size)
    srv = TokenServer(eng, tok, batch=args.batch, chunk=args.chunk,
                      paged=args.paged, page=args.page,
                      num_pages=args.num_pages,
                      replica_id=args.replica_id)
    print(f"PORT={srv.port}", flush=True)

    def _watch_stdin():
        try:
            sys.stdin.read()
        except OSError:
            pass
        srv.stop()

    threading.Thread(target=_watch_stdin, daemon=True).start()
    srv.serve_forever()
    return 0


if __name__ == "__main__":
    sys.exit(_main())
