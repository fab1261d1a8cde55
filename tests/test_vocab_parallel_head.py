"""The vocabulary-parallel LM head (DenseLLM.vocab_axis): at TP=N a
chip holds and reads V/N columns of the head, the logits stay split
over the vocabulary from the head to the pick, and only a token id a
slot crosses chips.

What is pinned here: where the head's leaves live (bf16 and int8, on
every constructor's path and on the path of a caller that hands
`place_replicated` a head already replicated over the mesh); that the
greedy pick over split logits is `jnp.argmax`'s over the gathered row,
ties across shards included, with and without a grammar mask; that the
compiled decode scan moves nothing of the logits' size between chips;
that the carry's placement is the tick's own (one compile of the scan,
none after warm-up); the `lm_head_shards` gauge; and TP=4 streams
against TP=1, greedy with a grammar in the batch and sampled.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from triton_dist_tpu.models import (AutoLLM, ContinuousScheduler, Engine,
                                    Request)
from triton_dist_tpu.models.config import tiny_qwen3
from triton_dist_tpu.models.dense import DenseLLM
from triton_dist_tpu.models.utils import place_replicated

_TP = 4
# V / _TP = 256 columns a chip: wider than the hidden size (64), so a
# [B, D] all-reduce of the layers is SMALLER than a chip's logits and
# the HLO test below can tell them apart
_V = 1024
_MODELS = {}
_ENGINES = {}


def _model(n, vocab=_V):
    if (n, vocab) not in _MODELS:
        if len(jax.devices()) < n:
            pytest.skip(f"needs >= {n} devices")
        cfg = tiny_qwen3(_TP, vocab_size=vocab)
        _MODELS[n, vocab] = (cfg, AutoLLM.from_config(
            cfg, jax.make_mesh((n,), ("tp",))))
    return _MODELS[n, vocab]


def _engine(n, **kw):
    key = (n,) + tuple(sorted(kw.items()))
    if key not in _ENGINES:
        _ENGINES[key] = Engine(_model(n)[1], max_seq=64,
                               backend="flash", **kw)
    return _ENGINES[key]


def _columns(x):
    """The shape of what each device holds of x."""
    return {s.data.shape for s in x.addressable_shards}


# ----------------------------------------------------------------------
# placement
# ----------------------------------------------------------------------

@pytest.mark.parametrize("n,vocab,shards", [
    (_TP, _V, _TP),          # split: the axis divides the vocabulary
    (_TP, _V - 2, 1),        # V % n != 0: replicated
    (1, _V, 1),              # one chip: replicated
])
def test_head_is_split_where_the_axis_divides_the_vocabulary(
        n, vocab, shards):
    cfg, model = _model(n, vocab)
    D = cfg.hidden_size
    assert model.vocab_axis == ("tp" if shards > 1 else None)
    want = P(None, "tp") if shards > 1 else P()
    assert model.lm_head.sharding.is_equivalent_to(
        NamedSharding(model.mesh, want), 2)
    assert _columns(model.lm_head) == {(D, vocab // shards)}
    # the embedding stays whole on every chip (a step reads B rows)
    assert _columns(model.embed) == {(vocab, D)}
    eng = Engine(model, max_seq=64, backend="flash")
    assert eng.lm_head_shards == shards
    assert eng.logits_sharding.is_equivalent_to(
        NamedSharding(model.mesh, want), 2)


def test_int8_head_has_both_leaves_split():
    cfg, model = _model(_TP)
    q = model.quantize_int8().lm_head
    assert _columns(q.q) == {(cfg.hidden_size, _V // _TP)}
    assert _columns(q.s) == {(_V // _TP,)}
    assert q.s.sharding.is_equivalent_to(
        NamedSharding(model.mesh, P("tp")), 1)
    # one chip: nothing to split
    q1 = _model(1)[1].quantize_int8().lm_head
    assert _columns(q1.q) == {(cfg.hidden_size, _V)}


def test_a_head_handed_over_replicated_is_split_in_place():
    """The benchmark's builder makes the head REPLICATED over the mesh
    and ends with place_replicated(model, mesh): the columns a chip
    keeps are its own, bit for bit, and the rest of the tree is left
    where it was."""
    cfg, model = _model(_TP)
    whole = jax.device_put(np.asarray(model.lm_head),
                           NamedSharding(model.mesh, P()))
    assert _columns(whole) == {(cfg.hidden_size, _V)}
    built = DenseLLM(**{f.name: getattr(model, f.name)
                        for f in dataclasses.fields(model)
                        if f.name != "lm_head"}, lm_head=whole)
    placed = place_replicated(built, model.mesh)
    assert _columns(placed.lm_head) == {(cfg.hidden_size, _V // _TP)}
    np.testing.assert_array_equal(np.asarray(placed.lm_head),
                                  np.asarray(whole))
    assert placed.embed is model.embed
    assert placed.layers[0].mlp.w_down is model.layers[0].mlp.w_down
    # under a trace there is nothing to place
    shapes = jax.eval_shape(lambda m: place_replicated(m, m.mesh), built)
    assert shapes.lm_head.shape == whole.shape


# ----------------------------------------------------------------------
# the pick
# ----------------------------------------------------------------------

def _tied_logits(B):
    """Rows whose maximum is attained in two DIFFERENT shards of 256
    columns (and one tie inside a shard), over a noise floor."""
    rng = np.random.RandomState(0)
    x = rng.randn(B, _V).astype(np.float32)
    ties = [(70, 900), (10, 300), (520, 530), (1023, 255)]
    for b, ids in enumerate(ties):
        x[b, list(ids)] = 50.0 + b
    return x, ties


@pytest.mark.parametrize("masked", [False, True],
                         ids=["plain", "grammar_mask"])
def test_greedy_pick_over_split_logits_is_argmax_of_the_row(masked):
    """One step of the engine's own paged scan from a crafted carry:
    the token a slot emits is jnp.argmax's of the gathered row (equal
    maxima go to the LOWER id, whatever shard holds it); under a mask
    that forbids the lower one, the other wins."""
    B = 4
    eng = _engine(_TP)
    x, ties = _tied_logits(B)
    mask = None
    if masked:
        mask = np.ones((B, _V), bool)
        for b, ids in enumerate(ties):
            mask[b, min(ids)] = False
        mask[0, :256] = False            # a whole shard forbidden
    logits = jax.device_put(jnp.asarray(x), eng.logits_sharding)
    assert _columns(logits) == {(B, _V // _TP)}
    pc = eng.make_paged_slot_cache(B, page=8)
    toks, nxt, _, _, _ = eng.paged_slot_chunk(
        logits, pc, jnp.zeros((B,), jnp.int32), jnp.ones((B,), bool),
        chunk=1, mask=mask)
    sel = x if mask is None else np.where(mask, x, -np.inf)
    want = np.asarray(jnp.argmax(jnp.asarray(sel), axis=-1))
    np.testing.assert_array_equal(np.asarray(toks)[:, 0], want)
    assert list(want) == [max(t) if masked else min(t) for t in ties]
    # what the tick hands back is placed as what it was handed
    assert nxt.sharding.is_equivalent_to(eng.logits_sharding, 2)


_COLLECTIVE = re.compile(
    r"= .*?\b(all-gather|all-reduce|collective-permute|all-to-all|"
    r"reduce-scatter)(-start)?\(")
_SHAPE = re.compile(r"\b[a-z]+\d+\[([\d,]+)\]")


def _largest_collective(hlo: str):
    """(elements, line) of the widest array on any collective's line
    (its result and operands alike)."""
    worst = (0, "")
    for line in hlo.splitlines():
        if not _COLLECTIVE.search(line):
            continue
        for dims in _SHAPE.findall(line.split("replica_groups")[0]):
            n = int(np.prod([int(d) for d in dims.split(",")]))
            worst = max(worst, (n, line.strip()[:200]))
    return worst


def test_decode_scan_moves_nothing_of_the_logits_size():
    """The compiled paged decode scan at TP=4: the head's product is a
    chip's own V/4 columns, the pick crosses chips as [B] pairs, so no
    all-gather / all-reduce / collective-permute carries B x V or even
    B x V/4 elements. (The same scan with the carry handed over
    REPLICATED is the control: the partitioner must then slice or
    gather, and the test's reading of the HLO is shown to see it.)"""
    from triton_dist_tpu.models.engine import _jit_programs, _params_key
    B = 4
    cfg, model = _model(_TP)
    eng = _engine(_TP)
    scan = _jit_programs("flash", "greedy",
                         _params_key(eng._sample_params),
                         eng.prefill_backend)["paged_slot_scan"]
    pc = eng.make_paged_slot_cache(B, page=8)
    logits = jax.device_put(jnp.zeros((B, _V), jnp.float32),
                            eng.logits_sharding)
    hlo = scan.lower(model, logits, pc, jnp.zeros((B,), jnp.int32),
                     jnp.ones((B,), bool), gen_len=2).compile().as_text()
    n, line = _largest_collective(hlo)
    assert n > 0, "no collective at all: the pick never crossed chips"
    assert n < B * _V // _TP, (
        f"a collective of {n} elements (a chip's logits are "
        f"{B * _V // _TP}): {line}")
    # the reader sees a gather when there is one
    gathered = jax.jit(lambda x: jax.lax.with_sharding_constraint(
        x, NamedSharding(model.mesh, P()))).lower(logits).compile()
    assert _largest_collective(gathered.as_text())[0] >= B * _V // _TP


# ----------------------------------------------------------------------
# the carry's placement, the gauge, the streams
# ----------------------------------------------------------------------

def _requests(cfg, *, grammar=False, seed=0):
    rng = np.random.RandomState(seed)
    spec = [(5, 6), (9, 8), (3, 4), (12, 7), (7, 5)]
    out = [Request(rid=i, ids=rng.randint(0, cfg.vocab_size, size=(L,))
                   .astype(np.int32), gen_len=g, seed=100 + i)
           for i, (L, g) in enumerate(spec)]
    if grammar:
        from triton_dist_tpu.models.structured import (GrammarSpec,
                                                       byte_vocab)
        g = GrammarSpec.from_json_schema(
            {"type": "object", "properties": {"b": {"type": "boolean"}}},
            byte_vocab(cfg.vocab_size))
        out[1] = dataclasses.replace(out[1], gen_len=12, grammar=g)
    return out


def _run(eng, reqs, **kw):
    sched = ContinuousScheduler(eng, batch=3, paged=True, chunk=2, **kw)
    return sched.run([dataclasses.replace(r) for r in reqs]), sched


def _backend_compiles(role=None):
    """How many programs reached the compiler so far, by the engine's
    own accounting (runtime/telemetry.CompileAccounting)."""
    from triton_dist_tpu.runtime.telemetry import default_registry
    return sum(v for k, v in default_registry().snapshot().items()
               if k.startswith("program_compile_n{")
               and "stage=backend" in k
               and (role is None or f"program={role}," in k))


@pytest.mark.parametrize("n", [_TP, 1])
def test_scan_compiles_once_and_a_warm_burst_compiles_nothing(n):
    """The carry enters the first tick placed as every tick returns it:
    a cold burst (refill and re-arming included) compiles the decode
    scan ONCE, and the same burst again compiles nothing at all. A
    carry that entered replicated and came back split would compile
    the scan a second time, inside a serving window. (On a mesh of
    one the carry is replicated, under the very spec a tick returns.)"""
    cfg, _ = _model(n)
    eng = _engine(n)
    reqs = _requests(cfg, seed=3)
    fresh = ContinuousScheduler(eng, batch=3, paged=True, chunk=2)
    assert _columns(fresh.slots.logits) == {(3, _V // n)}, \
        "the carry is built replicated: its first arming reshards it"
    n0 = _backend_compiles("paged_slot_scan")
    out, sched = _run(eng, reqs)                   # batch 3, chunk 2:
    assert _backend_compiles("paged_slot_scan") - n0 == 1    # cold here
    assert _columns(sched.slots.logits) == {(3, _V // n)}
    assert sched.slots.logits.sharding == eng.logits_sharding
    all0 = _backend_compiles()
    again, _ = _run(eng, reqs)
    assert _backend_compiles() == all0, "a warm burst compiled"
    for r in reqs:
        np.testing.assert_array_equal(again[r.rid], out[r.rid])


@pytest.mark.parametrize("n,shards,kw,append", [
    (_TP, _TP, {}, 1), (1, 1, {}, 1), (1, 1, {"kv_dtype": "int8"}, 0)])
def test_path_gauges(n, shards, kw, append):
    """lm_head_shards, and kv_append_kernel: whether the decode append
    to the K/V pages is the one-launch kernel (the int8 pool scatters)."""
    from triton_dist_tpu.runtime.telemetry import prometheus_text
    sched = ContinuousScheduler(_engine(n, **kw), batch=2, paged=True,
                                chunk=2, page=8)
    st = sched.stats()
    assert st["lm_head_shards"] == shards
    assert st["tp_size"] == n
    assert st["kv_append_kernel"] == append
    text = prometheus_text(sched.slots.tele.registry) + "\n"
    assert f"tdtpu_lm_head_shards {shards:g}\n" in text
    assert f"tdtpu_kv_append_kernel {append:g}\n" in text


@pytest.mark.parametrize("mode", ["greedy_with_grammar", "sampled"])
def test_streams_tp4_equal_tp1(mode):
    """Token for token: the same requests through TP=1 (a whole head,
    plain argmax) and TP=4 (a quarter of the head a chip, the pick per
    shard and then across). Greedy with a grammar-masked stream in the
    batch; top-k sampling, where the partitioner may gather the logits
    but the draw must be the one chip's."""
    cfg, _ = _model(1)
    ekw = {} if mode != "sampled" else dict(sampling="top_k",
                                            temperature=0.8)
    reqs = _requests(cfg, grammar=(mode != "sampled"))
    out1, _ = _run(_engine(1, **ekw), reqs)
    outN, sched = _run(_engine(_TP, **ekw), reqs)
    for r in reqs:
        np.testing.assert_array_equal(
            outN[r.rid], out1[r.rid],
            err_msg=f"{mode}: rid={r.rid} diverged TP={_TP} vs TP=1")
    if mode != "sampled":
        assert sched.stats()["grammar_mask_tokens"] > 0
