"""Load from this process: client threads over the program's own wire
client, and what each request saw on the client's clock.

A chip belongs to one process, so the clients are threads beside the
server's thread. Each thread blocks in a socket read nearly all of its
life; the generator reports how late it ran, so that a starved
generator is not read as a fast server.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Callable, List, Optional

from benchmark import traffic


@dataclasses.dataclass
class Record:
    """One request, as its client saw it (time.perf_counter seconds)."""
    index: int
    prompt: object
    gen_len: int
    due: float                        # when the schedule wanted it sent
    sent: float = 0.0                 # when the client sent it
    first: Optional[float] = None     # first message with tokens
    last: Optional[float] = None      # last message with tokens
    n_first: int = 0                  # tokens in the first message
    tokens: list = dataclasses.field(default_factory=list)
    token_times: list = dataclasses.field(default_factory=list)
    done: bool = False
    error: Optional[str] = None
    ended: Optional[float] = None

    @property
    def ok(self) -> bool:
        return (self.done and self.error is None
                and len(self.tokens) == self.gen_len)


def run_request(send: Callable, rec: Record) -> None:
    """Drive one request to its end and fill `rec`."""
    rec.sent = time.perf_counter()
    try:
        for msg in send(rec.prompt, rec.gen_len):
            now = time.perf_counter()
            if msg.get("done"):
                rec.done = True
                rec.error = msg.get("error")
                break
            ids = msg.get("token_ids") or []
            if ids:
                if rec.first is None:
                    rec.first = now
                    rec.n_first = len(ids)
                rec.last = now
                rec.tokens.extend(int(t) for t in ids)
                rec.token_times.append((now, len(ids)))
    except Exception as e:              # a refused or broken request
        rec.error = f"{type(e).__name__}: {e}"
    rec.ended = time.perf_counter()


class ClosedLoop:
    """`clients` threads, each sending its next request when the last
    ends, until the window closes; requests in flight then finish."""

    def __init__(self, deck: traffic.Deck, send: Callable, clients: int):
        self.deck, self.send, self.clients = deck, send, clients
        self.records: List[Record] = []
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._threads: list = []

    def _next(self) -> Record:
        with self._lock:
            p = self.deck.next()
            rec = Record(index=p.index, prompt=p.prompt,
                         gen_len=p.gen_len, due=time.perf_counter())
            self.records.append(rec)
            return rec

    def _client(self):
        while not self._stop.is_set():
            run_request(self.send, self._next())

    def start(self) -> None:
        self._threads = [threading.Thread(target=self._client,
                                          name=f"bench-client-{i}", daemon=True)
                         for i in range(self.clients)]
        for t in self._threads:
            t.start()

    def close(self) -> None:
        """The window has closed: no client starts another request."""
        self._stop.set()

    def join(self, timeout: float) -> bool:
        end = time.perf_counter() + timeout
        for t in self._threads:
            t.join(max(0.0, end - time.perf_counter()))
        return not any(t.is_alive() for t in self._threads)


class OpenLoop:
    """Requests on a schedule, whether or not earlier ones have ended.
    One pacing thread starts one short-lived thread per request."""

    def __init__(self, deck: traffic.Deck, send: Callable, offsets):
        self.deck, self.send = deck, send
        self.offsets = list(offsets)
        self.records: List[Record] = []
        self._threads: list = []
        self._pacer = threading.Thread(target=self._pace,
                                       name="bench-pacer", daemon=True)
        self._t0 = 0.0

    def _pace(self):
        for off in self.offsets:
            due = self._t0 + off
            delay = due - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            p = self.deck.next(off)
            rec = Record(index=p.index, prompt=p.prompt,
                         gen_len=p.gen_len, due=due)
            self.records.append(rec)
            t = threading.Thread(target=run_request,
                                 args=(self.send, rec),
                                 name=f"bench-req-{p.index}", daemon=True)
            self._threads.append(t)
            t.start()

    def start(self) -> None:
        self._t0 = time.perf_counter()
        self._pacer.start()

    def close(self) -> None:
        pass                          # the schedule ends with the window

    def join(self, timeout: float) -> bool:
        end = time.perf_counter() + timeout
        self._pacer.join(max(0.0, end - time.perf_counter()))
        for t in list(self._threads):
            t.join(max(0.0, end - time.perf_counter()))
        return not (self._pacer.is_alive()
                    or any(t.is_alive() for t in self._threads))


def make_loop(mix: dict, deck: traffic.Deck, send: Callable, seed: int,
              seconds: float):
    if mix["loop"] == "closed":
        return ClosedLoop(deck, send, int(mix["clients"]))
    if mix["loop"] == "open":
        return OpenLoop(deck, send,
                        traffic.arrival_offsets(mix, seed, seconds))
    raise ValueError(f"unknown loop kind {mix['loop']!r}")
