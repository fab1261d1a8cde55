"""Structured generation subsystem: KV-fork parallel sampling +
grammar-constrained decoding (models/structured.py + the scheduler's
fork/mask/jump-ahead paths).

The contracts under test, all bitwise:
  - an n>1 request's fork children stream token-for-token what n
    sequential same-prompt requests at seeds seed..seed+n-1 would
    (greedy, sampled, spec=K, under pool pressure, preempted mid-fork)
    while prefilling the shared prompt exactly ONCE;
  - a grammar that never prunes the argmax leaves the stream untouched
    (masked == unconstrained), and jump-ahead (spec=K over the forced
    automaton run) changes throughput, never tokens;
  - every invalid structured request (bad n, fork over batch,
    non-paged fork, vocab mismatch, dead-end automaton)
    is refused loudly per-request — the loop survives, nothing leaks;
  - the fork/mask machinery compiles ZERO programs the plain paged
    loop did not already compile (the in-program mask operand rides
    the existing tick signatures — jit-cache-churn guard).

Fast tier keeps the greedy fork core, the mask unit, the churn guard
and the capability validations; the heavy differentials (sampled,
spec, pressure, soak, sockets) are marked slow per the tier-1 budget.
"""

import json
import logging
import socket
import threading

import jax
import numpy as np
import pytest

from triton_dist_tpu.models import (AutoLLM, ContinuousScheduler, Engine,
                                    Request)
from triton_dist_tpu.models.config import tiny_qwen3
from triton_dist_tpu.models.structured import (NO_FORCED, GrammarDrafter,
                                               GrammarSpec, byte_vocab,
                                               constrained_draft,
                                               window_masks)
from triton_dist_tpu.runtime.chaos import FaultInjector, dead_end_grammar

mesh = None
_CACHE = {}


def setup_module(module):
    global mesh
    mesh = jax.make_mesh((len(jax.devices()),), ("tp",))


def _engine(kind="greedy"):
    """Module-cached engines: the fast tier shares one model build and
    one warmed program set across tests (tier-1 budget)."""
    if kind not in _CACHE:
        cfg = tiny_qwen3(mesh.shape["tp"])
        model = AutoLLM.from_config(cfg, mesh)
        if kind == "sampled":
            eng = Engine(model, max_seq=64, backend="xla",
                         sampling="top_k", temperature=0.8)
        else:
            eng = Engine(model, max_seq=64, backend="xla")
        _CACHE[kind] = (cfg, model, eng)
    return _CACHE[kind]


def _prompt(cfg, n, seed):
    rng = np.random.RandomState(seed)
    return rng.randint(0, cfg.vocab_size, size=(n,)).astype(np.int32)


def _assert_no_leak(sched):
    pool = sched.slots.prefix.pool
    assert pool.available + pool.outstanding == pool.num_pages, \
        (pool.available, pool.outstanding, pool.num_pages)


def _drain(sched, acc):
    while not sched.idle:
        out, _ = sched.poll()
        for rid, t in out.items():
            acc.setdefault(rid, []).extend(np.asarray(t).tolist())
    return acc


# ----------------------------------------------------------------------
# host-side grammar units (no model, no jax programs)
# ----------------------------------------------------------------------


def test_grammar_fsm_units():
    """from_token_fsm semantics: allow rows, advance/dead/final, the
    scratch-walked forced run, and edge validation."""
    V = 8
    # "2" or "2 2": 0 --2--> 1(acc via 2) ... concretely 0-2->1-2->2
    g = GrammarSpec.from_token_fsm(
        n_states=3, vocab_size=V, edges=[(0, 2, 1), (1, 2, 2)],
        accept=[2])
    st = g.fresh()
    assert st.allows(2) and not st.allows(0)
    assert st.allowed_row().sum() == 1
    assert st.advance(2) and not st.is_final and not st.is_dead
    # one legal continuation => deterministic forced run, state untouched
    assert st.forced_run(5) == [2]
    assert st.state == 1
    assert st.advance(2) and st.is_final
    assert not st.allowed_row().any()          # final => all-False row
    # illegal token kills the automaton
    st2 = g.fresh()
    assert not st2.advance(3) and st2.is_dead
    assert not st2.allowed_row().any()
    # out-of-range edges are rejected at compile time
    with pytest.raises(ValueError):
        GrammarSpec.from_token_fsm(n_states=2, vocab_size=4,
                                   edges=[(0, 9, 1)], accept=[1])
    # the never-prunes anchor: allows everything, never terminates
    a = GrammarSpec.all_tokens(V).fresh()
    assert a.allowed_row().all()
    assert a.advance(5) and not a.is_final and not a.is_dead
    # the chaos arm's FSM strands exactly after `after` tokens
    d = dead_end_grammar(V, after=2).fresh()
    assert d.advance(0) and d.advance(7)
    assert d.is_dead and not d.is_final


def test_json_schema_compile_and_wire():
    """A compiled schema DFA emits valid conforming JSON on a greedy
    first-allowed walk, terminates (is_final), and rejects non-JSON
    openings; from_wire parses both wire forms and refuses garbage
    with the ValueError the server echoes."""
    vocab = byte_vocab(256)
    schema = {"type": "object",
              "properties": {"ok": {"type": "boolean"},
                             "n": {"type": "integer", "maxDigits": 2}}}
    g = GrammarSpec.from_json_schema(schema, vocab)
    st, out = g.fresh(), []
    for _ in range(200):
        if st.is_final:
            break
        row = st.allowed_row()
        assert row.any(), "schema DFAs never dead-end by construction"
        t = int(np.argmax(row))
        assert st.advance(t)
        out.append(t)
    assert st.is_final, "walk must terminate inside 200 tokens"
    text = "".join(chr(t) for t in out)
    json.loads(text)                       # syntactically valid JSON
    assert not g.fresh().advance(ord("x"))  # objects must open with {
    # wire forms
    w = GrammarSpec.from_wire({"type": "json_schema", "schema": schema},
                              vocab)
    assert w.vocab_size == g.vocab_size and w.n_states == g.n_states
    f = GrammarSpec.from_wire(
        {"type": "token_fsm", "n_states": 2,
         "edges": [[0, 65, 1]], "accept": [1]}, vocab)
    fst = f.fresh()
    assert fst.advance(65) and fst.is_final
    for bad in ("not a dict", {"type": "nope"}, {"type": "json_schema"},
                {"type": "token_fsm", "edges": "x"}):
        with pytest.raises(ValueError):
            GrammarSpec.from_wire(bad, vocab)


def test_constrained_draft_and_window_masks():
    """The spec=K hooks: base-draft filtering + forced extension with
    the forced_from accounting index, and per-position verify-window
    masks that stay all-True past a walk break."""
    V = 16
    # linear chain 1 2 3 4 5 then accept: every state forced
    g = GrammarSpec.from_token_fsm(
        n_states=6, vocab_size=V,
        edges=[(i, i + 1, i + 1) for i in range(5)], accept=[5])
    st = g.fresh()
    # pure jump-ahead: no base draft, forced from window index 1
    draft, ffrom = constrained_draft(st, 1, [], 3)
    assert draft == [2, 3, 4] and ffrom == 1
    assert st.state == 0                      # live state untouched
    # base tokens that stay legal are kept; forced picks up after
    draft, ffrom = constrained_draft(st, 1, [2, 3], 4)
    assert draft == [2, 3, 4, 5] and ffrom == 3
    # an illegal base token truncates the base portion at once
    draft, ffrom = constrained_draft(st, 1, [9, 2], 2)
    assert draft == [2, 3] and ffrom == 1
    # illegal seed => empty window, no forced accounting
    draft, ffrom = constrained_draft(st, 7, [], 3)
    assert draft == [] and ffrom == NO_FORCED
    # window masks: position j constrains the prediction after toks[:j+1]
    m = window_masks(g.fresh(), [1, 2, 3], 3)
    assert m.shape == (3, V)
    for j in range(3):
        assert m[j].sum() == 1 and int(np.argmax(m[j])) == j + 2
    # an illegal draft token breaks the walk; later rows stay all-True
    m = window_masks(g.fresh(), [1, 9, 3], 3)
    assert m[0].sum() == 1 and m[1].all() and m[2].all()
    # GrammarDrafter (the external Drafter-protocol face): re-walks the
    # generated suffix of history, then proposes the forced run
    dr = GrammarDrafter(g, prompt_len=2)
    assert dr.propose([7, 7, 1], 3) == [2, 3, 4]
    assert dr.propose([7, 7, 1, 2, 3, 4, 5], 3) == []   # final
    assert dr.propose([7, 7, 9], 3) == []               # dead history


# ----------------------------------------------------------------------
# fork core + mask unit + validations + churn guard (fast tier)
# ----------------------------------------------------------------------


def test_fork_greedy_matches_sequential():
    """The tentpole differential: one n=3 request == three sequential
    same-prompt requests on a cache-off scheduler, with the prompt
    prefilled once (skip_frac == (n-1)/n), fork counters live, the
    parent rid retired tokenless, and the pool conserved."""
    cfg, _, eng = _engine()
    prompt = _prompt(cfg, 9, seed=0)
    n = 3
    sched = ContinuousScheduler(eng, batch=4, chunk=4, paged=True,
                                page=4)
    got = sched.run([Request(rid="F", ids=prompt, gen_len=8, seed=5,
                             n=n)])
    seq = ContinuousScheduler(eng, batch=4, chunk=4, paged=True,
                              page=4, prefix_cache=False)
    ref = seq.run([Request(rid=k, ids=prompt, gen_len=8, seed=5 + k)
                   for k in range(n)])
    for k in range(n):
        np.testing.assert_array_equal(got[("F", k)], ref[k],
                                      err_msg=f"fork {k}")
    assert got["F"].size == 0     # the parent rid itself never streams
    st = sched.stats()
    assert st["fork_shared_pages"] > 0
    assert st["forks_active"] == 0            # all retired
    assert st["prefill_skip_frac"] == pytest.approx((n - 1) / n,
                                                    abs=0.02)
    _assert_no_leak(sched)
    _assert_no_leak(seq)


def test_grammar_mask_never_prunes_bitwise():
    """Mask unit: the all-tokens grammar rides the full masked-tick
    machinery (chunk collapses to 1, mask operands threaded) yet the
    stream is bitwise the unconstrained one — masking is filtering,
    never perturbation. Mask accounting must tick."""
    cfg, _, eng = _engine()
    prompt = _prompt(cfg, 9, seed=1)
    a = ContinuousScheduler(eng, batch=4, chunk=4, paged=True, page=4)
    got = a.run([Request(rid="g", ids=prompt, gen_len=8, seed=2,
                         grammar=GrammarSpec.all_tokens(
                             cfg.vocab_size))])
    b = ContinuousScheduler(eng, batch=4, chunk=4, paged=True, page=4)
    ref = b.run([Request(rid="u", ids=prompt, gen_len=8, seed=2)])
    np.testing.assert_array_equal(got["g"], ref["u"])
    assert a.stats()["grammar_mask_tokens"] >= 8
    _assert_no_leak(a)


def test_capability_validations_reject_loudly():
    """Every unsupported structured-generation combination is refused
    per-request with a precise reason (the server echoes these into
    {"done", "error"} messages) and the poll loop keeps serving."""
    cfg, model, eng = _engine()
    prompt = np.arange(1, 7, dtype=np.int32)
    sched = ContinuousScheduler(eng, batch=2, chunk=4, paged=True,
                                page=4)
    out = sched.run([
        Request(rid="n0", ids=prompt, gen_len=4, n=-1),
        Request(rid="big", ids=prompt, gen_len=4, n=3),
        Request(rid="voc", ids=prompt, gen_len=4,
                grammar=GrammarSpec.all_tokens(cfg.vocab_size + 1)),
        Request(rid="ok", ids=prompt, gen_len=4),
    ])
    assert "n must be >= 1, got -1" in sched.rejected["n0"]
    assert "exceeds the slot batch 2" in sched.rejected["big"]
    assert "grammar compiled for vocab" in sched.rejected["voc"]
    assert "ok" not in sched.rejected and len(out["ok"]) == 4
    _assert_no_leak(sched)
    # contiguous slots cannot share prefix pages
    s2 = ContinuousScheduler(eng, batch=4, chunk=4)
    s2.run([Request(rid="c", ids=prompt, gen_len=4, n=2)])
    assert "needs the paged KV pool" in s2.rejected["c"]


def _struct_soak(eng, cfg, seed):
    """One fork + one constrained request through a paged scheduler —
    the full structured surface in one run (same shapes across seeds)."""
    sched = ContinuousScheduler(eng, batch=4, chunk=4, paged=True,
                                page=4)
    g = GrammarSpec.from_json_schema(
        {"type": "object", "properties": {"b": {"type": "boolean"}}},
        byte_vocab(cfg.vocab_size))
    out = sched.run([
        Request(rid="f", ids=_prompt(cfg, 8, seed), gen_len=6,
                seed=seed, n=3),
        Request(rid="c", ids=_prompt(cfg, 8, seed + 50), gen_len=16,
                seed=seed, grammar=g),
    ])
    return out, sched


class _CompileCounter(logging.Handler):
    def __init__(self):
        super().__init__()
        self.names = []

    def emit(self, record):
        msg = record.getMessage()
        if msg.startswith("Compiling "):
            self.names.append(msg.split()[1])


def test_structured_no_new_programs():
    """Jit-cache-churn guard: forks ride the plain paged tick (a fork
    is just a slot whose pages alias the parent's) and masks ride the
    EXISTING tick signatures as operands — so a warmed fork+grammar
    soak must compile ZERO new programs on the next soak, i.e. zero
    per-poll churn in steady state."""
    cfg, _, eng = _engine()
    counter = _CompileCounter()
    logger = logging.getLogger("jax._src.interpreters.pxla")
    logger.addHandler(counter)
    prev = jax.config.jax_log_compiles
    jax.config.update("jax_log_compiles", True)
    try:
        _struct_soak(eng, cfg, seed=3)       # compiles + warms
        n_warm = len(counter.names)
        _, sched = _struct_soak(eng, cfg, seed=9)
        new = counter.names[n_warm:]
        assert not new, (f"steady-state fork+grammar soak compiled "
                         f"{len(new)} new program(s): {new}")
    finally:
        jax.config.update("jax_log_compiles", prev)
        logger.removeHandler(counter)
    _assert_no_leak(sched)


# ----------------------------------------------------------------------
# heavy differentials (slow tier)
# ----------------------------------------------------------------------


@pytest.mark.slow
def test_fork_sampled_matches_sequential():
    """Sampled forks: child k's PRNG chain is the single-request chain
    at seed+k, so the n=3 burst equals three sequential sampled
    requests — and the streams actually diversify (the point of
    parallel sampling)."""
    cfg, _, eng = _engine("sampled")
    prompt = _prompt(cfg, 9, seed=4)
    sched = ContinuousScheduler(eng, batch=4, chunk=4, paged=True,
                                page=4)
    got = sched.run([Request(rid="S", ids=prompt, gen_len=10, seed=11,
                             n=3)])
    seq = ContinuousScheduler(eng, batch=4, chunk=4, paged=True,
                              page=4, prefix_cache=False)
    ref = seq.run([Request(rid=k, ids=prompt, gen_len=10, seed=11 + k)
                   for k in range(3)])
    for k in range(3):
        np.testing.assert_array_equal(got[("S", k)], ref[k],
                                      err_msg=f"fork {k}")
    assert len({tuple(got[("S", k)].tolist()) for k in range(3)}) >= 2
    _assert_no_leak(sched)


@pytest.mark.slow
def test_fork_spec_matches_plain_sequential():
    """Forks compose with speculative decoding: n=3 at spec=2 (greedy)
    equals three sequential spec=0 requests — the verify windows run
    on aliased pages without perturbing a single token."""
    cfg, _, eng = _engine()
    prompt = _prompt(cfg, 9, seed=5)
    sched = ContinuousScheduler(eng, batch=4, chunk=4, paged=True,
                                page=4, spec=2)
    got = sched.run([Request(rid="K", ids=prompt, gen_len=10, seed=3,
                             n=3)])
    seq = ContinuousScheduler(eng, batch=4, chunk=4, paged=True,
                              page=4, prefix_cache=False)
    ref = seq.run([Request(rid=k, ids=prompt, gen_len=10, seed=3 + k)
                   for k in range(3)])
    for k in range(3):
        np.testing.assert_array_equal(got[("K", k)], ref[k],
                                      err_msg=f"fork {k}")
    _assert_no_leak(sched)


@pytest.mark.slow
def test_fork_preempted_mid_stream_resumes_bitwise():
    """Preempt-mid-fork: a chaos-injected PoolExhausted while the fork
    family is live preempts one fork child (CoW pages released, request
    requeued) and it resumes through ordinary admission — every stream
    bitwise the undisturbed run's."""
    cfg, _, eng = _engine()
    p1, p2 = _prompt(cfg, 9, seed=6), _prompt(cfg, 8, seed=7)

    def run(fault):
        sched = ContinuousScheduler(eng, batch=4, chunk=4, paged=True,
                                    page=4, fault=fault)
        acc = {}
        sched.submit(Request(rid="F", ids=p1, gen_len=16, seed=2, n=3))
        # one poll: parent + forks armed, first chunk emitted — the
        # family is now live AND eligible (banked progress)
        out, _ = sched.poll()
        for rid, t in out.items():
            acc.setdefault(rid, []).extend(np.asarray(t).tolist())
        sched.submit(Request(rid="G", ids=p2, gen_len=8, seed=9))
        _drain(sched, acc)
        _assert_no_leak(sched)
        return acc, sched

    ref, _ = run(None)
    # admission attempt 0 = the fork parent; attempt 1 = G, faulted
    got, sched = run(FaultInjector(exhaust_admissions=[1]))
    assert sched.preemptions >= 1
    assert sched.fault.injected["pool_exhausted"] == 1
    for rid in ref:
        np.testing.assert_array_equal(got[rid], ref[rid],
                                      err_msg=f"rid={rid}")


@pytest.mark.slow
def test_fork_under_real_pool_pressure():
    """Genuine pressure: a pool sized for ~2 full slots serving a fork
    burst plus followers — fork children overflow to ordinary
    admissions (prefix-cache hit keeps them bitwise) and evictions/
    preemptions fire for real. Streams must equal the ample-pool run."""
    cfg, _, eng = _engine()
    worst = -(-(10 + 8 + 4 - 1) // 4)        # pages per full slot head
    reqs = lambda: [
        Request(rid="F", ids=_prompt(cfg, 10, seed=8), gen_len=8,
                seed=1, n=3),
        Request(rid="A", ids=_prompt(cfg, 12, seed=9), gen_len=6,
                seed=2),
        Request(rid="B", ids=_prompt(cfg, 12, seed=10), gen_len=6,
                seed=3),
    ]
    ample = ContinuousScheduler(eng, batch=4, chunk=4, paged=True,
                                page=4)
    ref = ample.run(reqs())
    tight = ContinuousScheduler(eng, batch=4, chunk=4, paged=True,
                                page=4,
                                num_pages=2 * worst + 1 + 1)
    got = tight.run(reqs())
    for rid in ref:
        np.testing.assert_array_equal(got[rid], ref[rid],
                                      err_msg=f"rid={rid}")
    _assert_no_leak(tight)


@pytest.mark.slow
def test_grammar_json_stream_and_jump_ahead_bitwise():
    """Constrained decode end-to-end: a JSON-schema request emits
    valid conforming JSON and finishes EARLY at is_final; jump-ahead
    (spec=2 riding the forced automaton run through the verify path)
    is bitwise identical to spec=0, with the jump accounting live.
    The external GrammarDrafter (Drafter protocol) is also bitwise
    neutral on an unconstrained greedy stream."""
    cfg, _, eng = _engine()
    prompt = _prompt(cfg, 8, seed=11)
    g = GrammarSpec.from_json_schema(
        {"type": "object",
         "properties": {"answer": {"type": "boolean"},
                        "count": {"type": "integer", "maxDigits": 3}}},
        byte_vocab(cfg.vocab_size))
    gen = 40

    def run(spec):
        sched = ContinuousScheduler(eng, batch=2, chunk=4, paged=True,
                                    page=4, spec=spec)
        out = sched.run([Request(rid="j", ids=prompt, gen_len=gen,
                                 seed=0, grammar=g)])
        _assert_no_leak(sched)
        return out["j"], sched

    off, _ = run(0)
    on, sched = run(2)
    np.testing.assert_array_equal(on, off)
    assert sched.stats()["jump_ahead_tokens"] > 0
    assert sched.stats()["grammar_mask_tokens"] > 0
    assert len(on) < gen, "is_final must finish the stream early"
    text = "".join(chr(int(t) % 256) for t in on)
    json.loads(text)
    # protocol face: a grammar drafter proposing schema continuations
    # against an UNCONSTRAINED greedy stream can only be rejected or
    # accepted by verify — never change the tokens
    plain = ContinuousScheduler(eng, batch=2, chunk=4, paged=True,
                                page=4)
    want = plain.run([Request(rid="u", ids=prompt, gen_len=12,
                              seed=0)])["u"]
    drafted = ContinuousScheduler(
        eng, batch=2, chunk=4, paged=True, page=4, spec=2,
        drafter=GrammarDrafter(g, prompt_len=len(prompt)))
    got = drafted.run([Request(rid="u", ids=prompt, gen_len=12,
                               seed=0)])["u"]
    np.testing.assert_array_equal(got, want)


@pytest.mark.slow
def test_dead_end_grammar_rejected_zero_leak():
    """The chaos arm: an automaton that strands after 2 tokens must
    produce a loud per-request 'grammar dead end' error, a retired
    slot, a surviving poll loop, and a conserved pool."""
    cfg, _, eng = _engine()
    sched = ContinuousScheduler(eng, batch=2, chunk=4, paged=True,
                                page=4)
    out = sched.run([
        Request(rid="d", ids=_prompt(cfg, 8, seed=12), gen_len=10,
                grammar=dead_end_grammar(cfg.vocab_size, after=2)),
        Request(rid="ok", ids=_prompt(cfg, 8, seed=13), gen_len=6),
    ])
    assert "grammar dead end after 2 tokens" in sched.rejected["d"]
    assert len(out["d"]) == 2                 # tokens before the wall
    assert len(out["ok"]) == 6                # the loop kept serving
    assert sched.stats()["forks_active"] == 0
    _assert_no_leak(sched)


@pytest.mark.slow
def test_structured_overlap_matches_sync():
    """overlap=True on a fork + constrained mix: grammar polls collapse
    the pipeline to the sync iteration (the next mask needs the
    unlanded token), unconstrained polls overlap — streams stay
    bitwise either way."""
    cfg, _, eng = _engine()
    g = GrammarSpec.from_json_schema(
        {"type": "object", "properties": {"b": {"type": "boolean"}}},
        byte_vocab(cfg.vocab_size))
    reqs = lambda: [
        Request(rid="f", ids=_prompt(cfg, 9, seed=14), gen_len=8,
                seed=1, n=2),
        Request(rid="c", ids=_prompt(cfg, 8, seed=15), gen_len=16,
                seed=2, grammar=g),
        Request(rid="p", ids=_prompt(cfg, 7, seed=16), gen_len=8,
                seed=3),
    ]
    sync = ContinuousScheduler(eng, batch=4, chunk=4, paged=True,
                               page=4)
    ref = sync.run(reqs())
    over = ContinuousScheduler(eng, batch=4, chunk=4, paged=True,
                               page=4, overlap=True)
    got = over.run(reqs())
    for rid in ref:
        np.testing.assert_array_equal(got[rid], ref[rid],
                                      err_msg=f"rid={rid}")
    _assert_no_leak(over)


@pytest.mark.slow
def test_fork_cancel_preempt_soak_zero_leak():
    """Randomized soak: fork bursts, grammar arms, mid-stream cancels
    of individual fork children, chaos-injected preemptions — after
    draining, the pool is conserved, no fork is live, and the race
    checker stays clean."""
    from triton_dist_tpu.analysis.races import check_scheduler
    cfg, _, eng = _engine()
    rng = np.random.RandomState(0)
    sched = ContinuousScheduler(
        eng, batch=4, chunk=4, paged=True, page=4,
        fault=FaultInjector(exhaust_admissions=[5, 11]))
    live = set()
    for i in range(8):
        n = int(rng.randint(1, 4))
        gram = (GrammarSpec.all_tokens(cfg.vocab_size)
                if n == 1 and rng.rand() < 0.4 else None)
        sched.submit(Request(
            rid=f"r{i}", ids=_prompt(cfg, int(rng.randint(4, 12)),
                                     seed=100 + i),
            gen_len=int(rng.randint(4, 10)), seed=i, n=n,
            grammar=gram))
        for _ in range(int(rng.randint(1, 4))):
            out, done = sched.poll()
            live.update(rid for rid, t in out.items() if len(t))
            live.difference_update(done)
        if live and rng.rand() < 0.5:
            victim = sorted(live, key=str)[int(rng.randint(len(live)))]
            sched.cancel(victim)            # fork children included
            live.discard(victim)
    _drain(sched, {})
    _assert_no_leak(sched)
    assert sched.stats()["forks_active"] == 0
    report = check_scheduler(sched)
    assert not report.errors, [f.format() for f in report.errors]


@pytest.mark.slow
def test_serving_fork_and_grammar_wire():
    """The TokenServer wire surface: structured refusals for bad n /
    over-cap n / malformed grammar / dead-end automaton (the reader
    thread never dies), an n=4 burst demuxed by fork tag with ONE
    fan-in done message, a schema-constrained stream decoding to valid
    JSON, the fork/grammar stats surface, and a conserved pool."""
    from triton_dist_tpu.serving import (ByteTokenizer, TokenServer,
                                         request_stream)
    cfg, _, eng = _engine()
    srv = TokenServer(eng, ByteTokenizer(cfg.vocab_size), batch=6,
                      chunk=4, paged=True, page=4, max_forks=4)
    threading.Thread(target=srv.serve_forever, daemon=True).start()

    def raw(payload):
        s = socket.create_connection((srv.host, srv.port), timeout=60)
        with s, s.makefile("rw") as f:
            f.write(json.dumps(payload) + "\n")
            f.flush()
            return [json.loads(l) for l in f]

    try:
        dead = {"type": "token_fsm", "n_states": 2, "vocab_size": 256,
                "edges": [[0, t, 1] for t in range(256)], "accept": []}
        for payload, frag in [
            ({"prompt": "hi", "n": 0}, "bad n=0"),
            ({"prompt": "hi", "n": 9}, "max_forks"),
            ({"prompt": "hi", "grammar": "nope"}, "JSON object"),
            ({"prompt": "hi", "grammar": {"type": "wat"}},
             "bad request"),
        ]:
            msgs = raw(payload)
            assert len(msgs) == 1 and msgs[0]["done"], (payload, msgs)
            assert frag in msgs[0]["error"], (payload, msgs)
        # dead-end automaton over the wire: accepted, then refused
        # loudly mid-stream via the fan-in done message
        msgs = raw({"prompt": "abcd", "gen_len": 8, "grammar": dead})
        assert msgs[-1]["done"]
        assert "grammar dead end" in msgs[-1]["error"], msgs[-1]
        # n=4 burst: streams tagged with fork k, one fan-in done
        msgs = raw({"prompt": "abcdefgh", "gen_len": 6, "n": 4,
                    "seed": 7})
        done = msgs[-1]
        assert done.get("done") and "error" not in done, done
        streams = {}
        for m in msgs[:-1]:
            streams.setdefault(m["fork"], []).extend(m["token_ids"])
        assert sorted(streams) == [0, 1, 2, 3]
        assert all(len(v) == 6 for v in streams.values())
        assert done["n_tokens"] == 24, done
        # schema-constrained stream decodes to valid JSON
        schema = {"type": "object",
                  "properties": {"a": {"type": "integer",
                                       "maxDigits": 2}}}
        msgs = list(request_stream(
            srv.host, srv.port, "abcdefgh", gen_len=30,
            grammar={"type": "json_schema", "schema": schema}))
        assert msgs[-1].get("done") and "error" not in msgs[-1]
        json.loads("".join(m["text"] for m in msgs[:-1]))
        st = srv.stats()
        assert st["forks_active"] == 0
        assert st["fork_shared_pages"] > 0
        assert st["grammar_mask_tokens"] > 0
    finally:
        srv.stop()
    pool = srv.sched.slots.prefix.pool
    assert pool.available + pool.outstanding == pool.num_pages
