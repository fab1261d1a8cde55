"""The seam `benchmark/systems/token_server.py` holds the program by.

A PR that may not touch `benchmark/` still has to keep every name the
adapter reaches into alive: the attributes `annotate()` wraps, the
handles `stats()` / `lifecycle()` / `pool_pages()` read, and the
counters the readers take from `stats()` (a reader that misses a key
reads 0 or null, it does not fail). This file builds the adapter's own
`Served` once, on the self-check's tiny configuration, and gives every
such name a case of its own, so that a rename fails under its name here
and not as a null metric on the chip. It reads `benchmark/`, and edits
nothing there.
"""

import collections
import json
import os
import sys
import threading

import jax
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)        # `benchmark` is a top-level package

# what annotate() wraps, as paths from the TokenServer
_WRAPPED = ("sched.poll", "sched._admit", "sched.slots.step_chunk",
            "sched.slots._fetch", "_emit", "_probe_disconnects",
            "_stop.wait", "_sock.accept")
# what stats() / lifecycle() / pool_pages() read
_HANDLES = ("stats", "sched.tele.export",
            "sched.slots.prefix.pool.num_pages")
# the keys of stats() that a reader or the harness takes by name
# (benchmark/readers/*.py, benchmark/metrics/*.json, harness.py)
_STATS_KEYS = ("device_wait_s_by_kind", "host_phase_s", "tokens_emitted",
               "engine_decode_dispatches", "engine_prefill_dispatches",
               "ticks_dispatched_ahead", "serve_loop_iterations",
               "prompt_tokens", "prefill_tokens_skipped", "admissions",
               "hits", "preemptions",
               # readers/compile_seconds.py sums the series of this
               # form; `eager` is the role every process has
               "program_compile_s{program=eager,stage=trace}",
               "program_compile_s{program=eager,stage=lower}",
               "program_compile_s{program=eager,stage=backend}",
               "program_compile_n{program=eager,stage=backend}",
               "program_compile_n{program=eager,stage=cache_load}")
# the wrapped hooks a dispatch-ahead serve loop enters while it serves.
# `_sock.accept` is the ACCEPTOR thread's call since PR 37: it reads
# `srv._sock` anew every pass, so it picks up the proxy that annotate()
# puts there on a running server (if it did not, `bench:accept_wait`
# would leave `breakdown.idle_gaps` in silence). NOT entered since then:
# `_stop.wait` (the `bench:idle_sleep` mark): an idle loop sleeps on its
# inbox's wake event; the name stays, `stop()` and `kill()` set the
# event. Nor `sched.slots.step_chunk` under dispatch-ahead (PR 32).
_ENTERED = ("sched.poll", "sched._admit", "sched.slots._fetch", "_emit",
            "_probe_disconnects", "_sock.accept")


def _resolve(root, path):
    *parents, attr = path.split(".")
    for name in parents:
        root = getattr(root, name)
    return root, attr


@pytest.fixture(scope="module")
def served():
    from benchmark.systems.token_server import Served
    from triton_dist_tpu import finalize_distributed
    cache_dir = jax.config.jax_compilation_cache_dir
    with open(os.path.join(_REPO, "benchmark", "testdata",
                           "tiny-qwen3.json")) as f:
        cfg = json.load(f)
    s = Served(cfg, 2**31 + 34, jax.devices()[:1], trace=True)
    try:
        yield s
    finally:
        s.stop()
        assert not s.errors, s.errors
        finalize_distributed()
        jax.config.update("jax_compilation_cache_dir", cache_dir)


@pytest.mark.parametrize("path", _WRAPPED)
def test_annotate_finds_what_it_wraps(served, path):
    obj, attr = _resolve(served.srv, path)
    assert callable(getattr(obj, attr)), path


@pytest.mark.parametrize("path", _HANDLES)
def test_adapter_handle_is_there(served, path):
    obj, attr = _resolve(served.srv, path)
    assert hasattr(obj, attr), path


def test_adapter_reads_through_its_handles(served):
    assert served.pool_pages() > 0
    assert isinstance(served.lifecycle(), dict)
    assert served.host and served.port


@pytest.mark.parametrize("key", _STATS_KEYS)
def test_stats_carries_the_key_a_reader_takes(served, key):
    assert key in served.stats(), key


def test_wrapped_hooks_are_entered_while_serving(served):
    """annotate() as the harness calls it (on a running server), over
    counting wrappers: two requests later the hooks the dispatch-ahead
    loop calls were each entered, and the counters the per-layer
    metrics read have the form the readers expect."""
    from benchmark.systems.token_server import request
    counts = collections.Counter()

    def count(path):
        obj, attr = _resolve(served.srv, path)
        inner = getattr(obj, attr)

        def outer(*a, **kw):
            counts[path] += 1
            return inner(*a, **kw)
        setattr(obj, attr, outer)

    for path in _WRAPPED[:-1]:        # a socket takes no new attribute
        count(path)
    before = served.stats()
    served.annotate()
    count("_sock.accept")             # the adapter's proxy does

    streams = {}

    def client(i):
        streams[i] = list(request(served.host, served.port,
                                  [3 + i, 5, 7, 11, 13], 8, 300.0))

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(600.0)
        assert not t.is_alive()
    for msgs in streams.values():
        assert msgs[-1].get("done") and not msgs[-1].get("error"), msgs[-1]
        assert sum(len(m.get("token_ids") or []) for m in msgs) == 8

    after = served.stats()
    print("wrapped hooks entered:", dict(counts))
    for path in _ENTERED:
        assert counts[path] > 0, (path, dict(counts))
    by_kind = after["device_wait_s_by_kind"]
    assert isinstance(by_kind, dict) and by_kind
    assert all(isinstance(v, (int, float)) for v in by_kind.values())
    assert sum(by_kind.values()) > sum(
        before["device_wait_s_by_kind"].values())
    assert after["ticks_dispatched_ahead"] > before["ticks_dispatched_ahead"]
    assert set(served.lifecycle()) and all(
        ev[1] for evs in served.lifecycle().values() for ev in evs)


def _compile_metric(name, stats0, stats1):
    """`benchmark/metrics/<name>.json` through its reader, over a
    capture that holds the two snapshots and nothing else."""
    import importlib
    import types
    with open(os.path.join(_REPO, "benchmark", "metrics",
                           name + ".json")) as f:
        m = json.load(f)
    assert m["reader"] == "compile_seconds", name
    reader = importlib.import_module("benchmark.readers." + m["reader"])
    cap = types.SimpleNamespace(stats0=stats0, stats1=stats1)
    return reader.read(cap, **m["args"])


def test_compile_seconds_reads_the_fixtures_own_stats(served):
    """The six `setup.*` / `serve.compile_in_window_ms.*` metric files
    over the adapter's own `stats()`: at set-up each reads a number
    (the tiny server's build traced, lowered and compiled something,
    under an engine role too once a request has run), a window in
    which nothing compiles reads 0.0 and not None, and a program that
    keeps no such counters reads None."""
    from benchmark.systems.token_server import request
    msgs = list(request(served.host, served.port, [2, 3, 5, 7, 11], 4,
                        300.0))
    assert msgs[-1].get("done") and not msgs[-1].get("error"), msgs[-1]
    st = served.stats()
    at_setup = {name: _compile_metric(name, st, st) for name in (
        "setup.trace_s", "setup.lower_s", "setup.backend_s",
        "setup.cache_hit_pct", "setup.programs_n")}
    print("compile_seconds at set-up:", at_setup)
    for name in ("setup.trace_s", "setup.lower_s", "setup.backend_s"):
        assert at_setup[name] > 0.0, name
    assert 0.0 <= at_setup["setup.cache_hit_pct"] <= 100.0
    # the admission, the decode scan, the table reset at least
    assert at_setup["setup.programs_n"] >= 3
    assert at_setup["setup.programs_n"] == sum(
        v for k, v in st.items()
        if k.startswith("program_compile_n{") and "stage=backend" in k
        and "program=eager" not in k)
    for name in ("serve.compile_in_window_ms.sat",
                 "serve.compile_in_window_ms.steady"):
        assert _compile_metric(name, st, dict(st)) == 0.0
        grown = dict(st)
        grown["program_compile_s{program=paged_admit,stage=backend}"] = \
            st.get("program_compile_s{program=paged_admit,stage=backend}",
                   0.0) + 0.25
        assert _compile_metric(name, st, grown) == pytest.approx(250.0)
    older = {k: v for k, v in st.items()
             if not k.startswith("program_compile_")}
    for name in list(at_setup) + ["serve.compile_in_window_ms.sat"]:
        assert _compile_metric(name, older, older) is None, name


# ----------------------------------------------------------------------
# the deepseek_v3 family's adapter (benchmark/systems/deepseek_server.py)
# on a tiny configuration of that family: the same names and handles
# ----------------------------------------------------------------------

# what a reader takes from this family's stats() beside _STATS_KEYS
_SHARE_KEYS = ("moe_pairs_routed", "moe_pairs_held", "kv_page_copy_bytes",
               "cache_bytes{kind=latent}", "cache_uniform_bytes")


@pytest.fixture(scope="module")
def served_share():
    from benchmark.systems.deepseek_server import Served
    from triton_dist_tpu import finalize_distributed
    cache_dir = jax.config.jax_compilation_cache_dir
    with open(os.path.join(_REPO, "benchmark", "testdata",
                           "tiny-deepseek-v3.json")) as f:
        cfg = json.load(f)
    s = Served(cfg, 2**31 + 36, jax.devices()[:1], trace=True)
    try:
        yield s
    finally:
        s.stop()
        assert not s.errors, s.errors
        finalize_distributed()
        jax.config.update("jax_compilation_cache_dir", cache_dir)


@pytest.mark.parametrize("path", _WRAPPED + _HANDLES)
def test_share_adapter_has_the_names_the_harness_takes(served_share, path):
    obj, attr = _resolve(served_share.srv, path)
    assert hasattr(obj, attr), path


def test_share_adapter_serves_and_counts(served_share):
    """One request through the wire; the handles read, and stats()
    carries every key a reader of this family's cell takes by name."""
    from benchmark.systems.deepseek_server import request
    s = served_share
    assert s.pool_pages() > 0 and s.weight_bytes > 0
    assert (s.batch, s.chunk) == (4, 4)
    before = s.stats()
    msgs = list(request(s.host, s.port, [3, 5, 7, 11, 13], 8, 300.0))
    assert msgs[-1].get("done") and not msgs[-1].get("error"), msgs[-1]
    assert sum(len(m.get("token_ids") or []) for m in msgs) == 8
    after = s.stats()
    for key in _STATS_KEYS + _SHARE_KEYS:
        assert key in after, key
    routed = after["moe_pairs_routed"] - before["moe_pairs_routed"]
    assert routed > 0 and routed % (4 * 4 * 2) == 0  # slots x k x layers
    assert 0 <= after["moe_pairs_held"] <= after["moe_pairs_routed"]
    assert isinstance(s.lifecycle(), dict) and s.lifecycle()


# ----------------------------------------------------------------------
# the keye_vl2 family's adapter (benchmark/systems/keye_server.py) on a
# tiny configuration of that family: what the `.longdoc` metric files
# (benchmark/metrics/*.longdoc.json) take from stats() by name
# ----------------------------------------------------------------------

_SPARSE_KEYS = ("sa_positions_in_context", "sa_positions_attended",
                "cache_bytes{kind=pages}", "cache_bytes{kind=index}",
                "cache_uniform_bytes", "moe_pairs_routed",
                "moe_pairs_held", "kv_page_copy_bytes",
                "moe_experts_touched", "moe_experts_offered")


@pytest.fixture(scope="module")
def served_sparse():
    from benchmark.systems.keye_server import Served, request
    from triton_dist_tpu import finalize_distributed
    cache_dir = jax.config.jax_compilation_cache_dir
    with open(os.path.join(_REPO, "benchmark", "testdata",
                           "tiny-keye-vl2.json")) as f:
        cfg = json.load(f)
    s = Served(cfg, 2**31 + 39, jax.devices()[:1], trace=True)
    try:
        before = s.stats()
        # 24 + 8 positions: past the tiny indexer's topk of 16
        msgs = list(request(s.host, s.port, list(range(3, 27)), 8, 300.0))
        assert msgs[-1].get("done") and not msgs[-1].get("error"), msgs[-1]
        yield s, before, s.stats()
    finally:
        s.stop()
        assert not s.errors, s.errors
        finalize_distributed()
        jax.config.update("jax_compilation_cache_dir", cache_dir)


@pytest.mark.parametrize("key", _STATS_KEYS + _SPARSE_KEYS)
def test_sparse_adapter_stats_carry_the_key_a_reader_takes(served_sparse,
                                                           key):
    _, _, after = served_sparse
    assert key in after, key


def test_sparse_adapter_counts_what_the_metric_files_divide(served_sparse):
    """`sa.attended_share_pct.longdoc` and `moe.held_pair_share_pct
    .longdoc` are ratios of these counters' deltas: both numerators
    move, and stay under their denominators."""
    s, before, after = served_sparse
    assert s.pool_pages() > 0 and (s.batch, s.chunk) == (4, 4)
    d = lambda k: after[k] - before.get(k, 0)  # noqa: E731
    assert 0 < d("sa_positions_attended") < d("sa_positions_in_context")
    assert 0 <= d("moe_pairs_held") < d("moe_pairs_routed")
    assert d("moe_pairs_routed") % (4 * 4 * 2) == 0  # slots x k x layers
    assert 0 < d("moe_experts_touched") <= d("moe_experts_offered")
    for name in os.listdir(os.path.join(_REPO, "benchmark", "metrics")):
        if not name.endswith(".longdoc.json"):
            continue
        with open(os.path.join(_REPO, "benchmark", "metrics", name)) as f:
            args = json.load(f).get("args", {})
        for k in ("numerator", "denominator"):
            assert args.get(k, "tokens_emitted") in after, (name, k)


# ----------------------------------------------------------------------
# the afmoe family's adapter (benchmark/systems/afmoe_server.py) on a
# tiny configuration of that family: what the `.agent` metric files
# (benchmark/metrics/*.agent.json) take from stats() by name
# ----------------------------------------------------------------------

_RING_KEYS = ("attn_kv_positions{kind=window}",
              "attn_kv_positions{kind=full}", "cache_bytes{kind=pages}",
              "cache_bytes{kind=window}", "cache_uniform_bytes",
              "moe_pairs_routed", "moe_pairs_held", "kv_page_copy_bytes",
              "moe_experts_touched", "moe_experts_offered")


@pytest.fixture(scope="module")
def served_rings():
    from benchmark.systems.afmoe_server import Served, request
    from triton_dist_tpu import finalize_distributed
    cache_dir = jax.config.jax_compilation_cache_dir
    with open(os.path.join(_REPO, "benchmark", "testdata",
                           "tiny-afmoe.json")) as f:
        cfg = json.load(f)
    s = Served(cfg, 2**31 + 43, jax.devices()[:1], trace=True)
    try:
        before = s.stats()
        # 24 + 8 positions: three and four times the tiny window of 8
        msgs = list(request(s.host, s.port, list(range(3, 27)), 8, 300.0))
        assert msgs[-1].get("done") and not msgs[-1].get("error"), msgs[-1]
        yield s, before, s.stats()
    finally:
        s.stop()
        assert not s.errors, s.errors
        finalize_distributed()
        jax.config.update("jax_compilation_cache_dir", cache_dir)


@pytest.mark.parametrize("key", _STATS_KEYS + _RING_KEYS)
def test_ring_adapter_stats_carry_the_key_a_reader_takes(served_rings, key):
    _, _, after = served_rings
    assert key in after, key


def test_ring_adapter_counts_what_the_metric_files_divide(served_rings):
    """`attn.window_read_share_pct.agent`, `moe.held_pair_share_pct
    .agent` and `moe.experts_touched_pct.agent` are ratios of these
    counters' deltas: the numerators move, and stay under their
    denominators; every series a `.agent` metric file names is in
    stats()."""
    s, before, after = served_rings
    assert s.pool_pages() > 0 and (s.batch, s.chunk) == (4, 4)
    d = lambda k: after[k] - before.get(k, 0)  # noqa: E731
    win, full = (d("attn_kv_positions{kind=window}"),
                 d("attn_kv_positions{kind=full}"))
    # the request's slot reads 8 rows of each of six rings a step and
    # 25-32 positions of each of two full layers
    assert 0 < win and 0 < full and win % 6 == 0 and full % 2 == 0
    assert 0 <= d("moe_pairs_held") < d("moe_pairs_routed")
    assert d("moe_pairs_routed") % (4 * 4 * 7) == 0  # slots x k x layers
    assert 0 < d("moe_experts_touched") <= d("moe_experts_offered")
    assert "cache_bytes{kind=state}" not in after
    for name in os.listdir(os.path.join(_REPO, "benchmark", "metrics")):
        if not name.endswith(".agent.json"):
            continue
        with open(os.path.join(_REPO, "benchmark", "metrics", name)) as f:
            args = json.load(f).get("args", {})
        for k in ("numerator", "denominator"):
            assert args.get(k, "tokens_emitted") in after, (name, k)
        for k in args.get("numerators", []) + args.get("denominators", []):
            assert k in after, (name, k)


# ----------------------------------------------------------------------
# the falcon_h1 family's adapter (benchmark/systems/falcon_h1_server.py)
# on a tiny configuration of that family: what the `.reply` metric
# files (benchmark/metrics/*.reply.json) take from stats() by name
# ----------------------------------------------------------------------

_STATE_KEYS = ("ssm_state_updates", "cache_bytes{kind=pages}",
               "cache_bytes{kind=state}", "cache_uniform_bytes",
               "kv_page_copy_bytes")


@pytest.fixture(scope="module")
def served_state():
    from benchmark.systems.falcon_h1_server import Served, request
    from triton_dist_tpu import finalize_distributed
    cache_dir = jax.config.jax_compilation_cache_dir
    with open(os.path.join(_REPO, "benchmark", "testdata",
                           "tiny-falcon-h1.json")) as f:
        cfg = json.load(f)
    s = Served(cfg, 2**31 + 47, jax.devices()[:1], trace=True)
    try:
        before = s.stats()
        msgs = list(request(s.host, s.port, list(range(3, 22)), 8, 300.0))
        assert msgs[-1].get("done") and not msgs[-1].get("error"), msgs[-1]
        yield s, before, s.stats()
    finally:
        s.stop()
        assert not s.errors, s.errors
        finalize_distributed()
        jax.config.update("jax_compilation_cache_dir", cache_dir)


@pytest.mark.parametrize("key", _STATS_KEYS + _STATE_KEYS)
def test_state_adapter_stats_carry_the_key_a_reader_takes(served_state,
                                                           key):
    _, _, after = served_state
    assert key in after, key


def test_state_adapter_counts_what_the_metric_files_divide(served_state):
    """`cache.state_bytes_pct.reply` divides the state gauge by state
    plus pages at the close; `ssm_state_updates` counts (live slot,
    layer) updates, chunk steps a tick; every series a `.reply` metric
    file names is in stats()."""
    s, before, after = served_state
    assert s.pool_pages() > 0 and (s.batch, s.chunk) == (4, 4)
    d = lambda k: after[k] - before.get(k, 0)  # noqa: E731
    # one live slot, 2 layers, 4 steps a tick: the 8 tokens took 2 ticks
    # or more (the first token comes from the admission)
    n = d("ssm_state_updates")
    assert n > 0 and n % (1 * 2 * 4) == 0
    assert "cache_bytes{kind=window}" not in after
    for name in os.listdir(os.path.join(_REPO, "benchmark", "metrics")):
        if not name.endswith(".reply.json"):
            continue
        with open(os.path.join(_REPO, "benchmark", "metrics", name)) as f:
            args = json.load(f).get("args", {})
        for k in ("numerator", "denominator"):
            assert args.get(k, "tokens_emitted") in after, (name, k)
        for k in args.get("numerators", []) + args.get("denominators", []):
            assert k in after, (name, k)
