"""The one traffic generator. A mix is a data file under
`benchmark/traffic/`; this module turns it and a seed into requests.

Every seed gets the SAME multiset of sizes and of arrival gaps, in
another order: sizes are the distribution's quantiles (a stratified
deck), gaps are the exponential's quantiles, and the seed only shuffles
them and draws the token ids. So runs with different seeds do the same
work, and what differs between them is noise, not the draw.

Mix file keys:
  loop         "closed" (clients, each sending its next request when the
               last ends) or "open" (arrivals on a schedule)
  clients      closed loop: how many
  rate_per_s   open loop: offered rate; arrivals "poisson" or "uniform"
  prompt_len   {"kind": "choice", "values": [...], "weights": [...]}
  output_len   {"kind": "uniform" | "log_uniform", "lo": a, "hi": b}
               or a "choice" as above
  deck         how many (prompt, output) pairs the stratified deck holds,
               or "window" (open loop): as many as are due in the window,
               so that every seed sends the whole deck, the same sizes,
               inside it (a larger deck's first part is another draw of
               sizes for every seed: 5,223-7,290 output tokens due in 40 s
               of `chat-steady` with a deck of 240)
  sharing      "none": every prompt's ids are drawn afresh
  sampling     "greedy"
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np

@dataclasses.dataclass
class Planned:
    index: int
    due_s: float              # open loop: offset from the window's start
    prompt: np.ndarray        # token ids
    gen_len: int


def _quantile_deck(dist: dict, n: int) -> np.ndarray:
    """n values of the distribution at the quantiles (i + 0.5) / n."""
    u = (np.arange(n) + 0.5) / n
    kind = dist["kind"]
    if kind == "choice":
        w = np.asarray(dist["weights"], float)
        cum = np.cumsum(w / w.sum())
        idx = np.minimum(np.searchsorted(cum, u, side="left"),
                         len(cum) - 1)
        return np.asarray(dist["values"])[idx].astype(int)
    lo, hi = float(dist["lo"]), float(dist["hi"])
    if kind == "uniform":
        return np.rint(lo + u * (hi - lo)).astype(int)
    if kind == "log_uniform":
        return np.rint(np.exp(math.log(lo) + u * (
            math.log(hi) - math.log(lo)))).astype(int)
    raise ValueError(f"unknown distribution kind {kind!r}")


def prompt_lengths(mix: dict) -> list:
    """Every prompt length the mix can send: what set-up must warm."""
    d = mix["prompt_len"]
    if d["kind"] != "choice":
        raise ValueError(
            "prompt_len must be a choice over exact lengths: each length "
            "is one admission program (PERF.md, what only the program "
            "can shorten)")
    return sorted(int(v) for v in d["values"])


def max_tokens(mix: dict) -> int:
    out = mix["output_len"]
    hi = max(out["values"]) if out["kind"] == "choice" else out["hi"]
    return max(prompt_lengths(mix)) + int(hi)


class Deck:
    """The seed's order of the mix's fixed deck of sizes, with fresh
    token ids for every request, handed out one at a time (thread-safe
    enough: one generator thread or a lock around `next`)."""

    WARM = 256          # first tokens kept for the warm-up's prompts

    def __init__(self, mix: dict, seed: int, vocab: int,
                 seconds: float = 0.0):
        if mix.get("sharing", "none") != "none":
            raise ValueError("only sharing 'none' is generated yet")
        if mix.get("sampling", "greedy") != "greedy":
            raise ValueError("only greedy sampling is checked yet")
        n = mix.get("deck", 240)
        n = arrivals_due(mix, seconds) if n == "window" else int(n)
        self._rng = np.random.default_rng(int(seed))
        # prompt and output lengths are paired independently: each is its
        # own stratified deck, shuffled apart
        self._plen = self._rng.permutation(
            _quantile_deck(mix["prompt_len"], n))
        self._olen = self._rng.permutation(
            _quantile_deck(mix["output_len"], n))
        self._vocab = int(vocab)
        # "no shared prefix" to the letter: every prompt of the run,
        # warm-up included, starts with a token of its own, so that no
        # chance match of first tokens sends an admission down the
        # prefix-hit path (and its small programs) inside the window
        self._first = self._rng.permutation(self._vocab)
        self._i = 0
        self.n = n

    def next(self, due_s: float = 0.0) -> Planned:
        i = self._i
        self._i += 1
        j = i % self.n
        prompt = self._ids(int(self._plen[j]), self.WARM + i)
        return Planned(index=i, due_s=float(due_s), prompt=prompt,
                       gen_len=int(self._olen[j]))

    def _ids(self, n: int, k: int) -> np.ndarray:
        ids = self._rng.integers(0, self._vocab, size=n,
                                 dtype=np.int64).astype(np.int32)
        ids[0] = self._first[k % self._vocab]
        return ids

    def warm_prompt(self, n: int, k: int) -> np.ndarray:
        """The k-th warm-up prompt (k < WARM), of n tokens."""
        if not 0 <= k < self.WARM:
            raise ValueError(f"warm-up prompt {k} of {self.WARM}")
        return self._ids(n, k)


def arrivals_due(mix: dict, seconds: float) -> int:
    """Open loop: how many requests are due inside the window."""
    if mix["loop"] != "open":
        raise ValueError('deck "window" needs an open loop')
    return max(1, int(round(float(mix["rate_per_s"]) * seconds)))


def arrival_offsets(mix: dict, seed: int, seconds: float) -> np.ndarray:
    """Open loop: when each request is due, as offsets into the window.
    The gaps are the exponential's quantiles for rate x seconds
    requests, shuffled by the seed: their sum, and so the number of
    requests due inside the window, is the same for every seed."""
    rate = float(mix["rate_per_s"])
    n = arrivals_due(mix, seconds)
    u = (np.arange(n) + 0.5) / n
    if mix.get("arrivals", "poisson") == "poisson":
        gaps = -np.log1p(-u) / rate
        gaps *= (seconds / gaps.sum())       # quantile grid's small bias
    else:
        gaps = np.full(n, 1.0 / rate)
    rng = np.random.default_rng(int(seed) ^ 0x5EED)
    gaps = rng.permutation(gaps)
    t = np.cumsum(gaps) - gaps[0] * 0.5
    return t[t < seconds]
