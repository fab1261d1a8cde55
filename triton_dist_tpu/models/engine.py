"""Inference engine (reference: `python/triton_dist/models/engine.py`
`Engine:37` — `serve():113` = prefill -> backend switch :126-135 ->
CUDA-graph capture `_init_cuda_graph:75` -> decode loop :166).

TPU re-design of the decode hot loop: the CUDA-graph analog is a single
jitted `lax.scan` over decode steps with a donated KV cache — one XLA
program for the whole generation, zero per-step host round-trips
(strictly stronger than graph replay, which still launches per step).

Backends (reference backend strings engine.py:126-135):
  "xla"     <- torch            (oracle)
  "flash"   <- single-chip framework path: Pallas flash-decode +
               fused SwiGLU kernels, no comm kernels
  "dist"    <- triton_dist      (AG-GEMM / GEMM-RS)
  "ar"      <- triton_dist_AR   (partial GEMMs + AR kernel)
  "gemm_ar" <- triton_dist_gemm_ar (fused GEMM+AR)
  "ep"      <- AG-GEMM / GEMM-RS attention + EP dispatch / combine FFN
               (an expert-sharded Qwen3MoE, models/qwen_moe.py)
  "ep_flash" <- the framework attention kernels + the same EP FFN
"""

from __future__ import annotations

import functools
import threading
from typing import Optional

import jax
import jax.numpy as jnp

from triton_dist_tpu.kernels.paged_kv import set_page_rows
from triton_dist_tpu.runtime.telemetry import (
    default_registry, install_compile_accounting, register_program_roles)

# every string Engine(backend=) serves: the module docstring's table
BACKENDS = ("xla", "flash", "dist", "ar", "gemm_ar", "ep", "ep_flash")


class Engine:
    def __init__(self, model, *, max_seq: int = 256, backend: str = "gemm_ar",
                 prefill_backend: Optional[str] = None,
                 kv_dtype=None, sampling: str = "greedy",
                 temperature: float = 1.0, top_k: int = 50,
                 top_p: float = 0.9):
        """kv_dtype=jnp.int8 stores the KV cache quantized (per-position
        scales; kv_cache.py) — half the decode step's dominant HBM read.
        Pair with model.quantize_int8() for the full bandwidth-bound
        decode configuration.

        sampling: "greedy" (default), "top_k" or "top_p" (reference:
        the sampling helpers of models/utils.py driven by the chat
        server, mega_triton_kernel/test/models/model_server.py). The
        non-greedy paths thread a PRNG key through the decode scan's
        carry (split per step); greedy keeps the key-free carry so the
        bench path is untouched. temperature=0 collapses every sampler
        to greedy."""
        self.model = model
        self.max_seq = max_seq
        self.backend = backend
        self.kv_dtype = kv_dtype
        if sampling not in ("greedy", "top_k", "top_p"):
            raise ValueError(f"unknown sampling mode {sampling!r}")
        # MODEL/BACKEND capability gate (ISSUE 13): every unsupported
        # combination refuses HERE, at construction, naming the missing
        # capability — not as a shape/attribute error deep inside the
        # first jitted forward.
        if backend not in BACKENDS:
            raise ValueError(f"unknown backend {backend!r}; this engine "
                             f"serves {BACKENDS}")
        self.moe_family = _is_moe(model)
        # what the model says of itself (models/utils.ServingTraits):
        # the ONE place the engine learns the pool's heads and
        # whether slots hold state beside pages
        self.traits = model.serving_traits()
        # what a slot of this model has that the engine's own paged
        # programs cannot move (None for K and V pages alone)
        self._own = self.traits.slot_state or self.traits.own_pool
        # a slot of such a model is pages PLUS state that pages cannot
        # express: what moves or rebuilds pages alone is refused, here
        # and wherever an option is taken, through refuse_slot_state
        if backend not in ("flash", "xla"):
            self.refuse_slot_state(
                f"backend={backend!r}",
                f"TP comm-kernel projections over "
                f"{self._own}; its layers run "
                f"single-chip on 'flash' or the 'xla' oracle")
        if kv_dtype is not None:
            self.refuse_slot_state(
                f"kv_dtype={jnp.dtype(kv_dtype)}",
                f"int8 pool for {self._own}")
        # SEQUENCE-PARALLEL serving (long-context — the sp-sharded
        # paged pool, kv_cache.PagedSlotCache SP SHARDING): capability
        # gates live HERE, at construction, naming what is missing —
        # the PR-13 pattern — instead of shape errors deep in jit.
        sp_ax = getattr(model, "sp_axis", None)
        self.sp_size = int(model.mesh.shape[sp_ax]) if sp_ax else 1
        if self.sp_size > 1:
            tp = model.mesh.shape[model.axis]
            if tp > 1:
                raise ValueError(
                    f"sequence-parallel serving (sp_axis={sp_ax!r}, "
                    f"size {self.sp_size}) cannot combine with a TP "
                    f"head-group split (axis {model.axis!r}, size "
                    f"{tp}) yet (missing capability: sp + TP hybrid "
                    f"paged pool) — size one of the axes to 1")
            if backend not in ("flash",):
                raise ValueError(
                    f"backend={backend!r} routes projections through "
                    f"the TP comm kernels; sequence-parallel serving "
                    f"replicates weights over the sp axis and serves "
                    f"on backend='flash' (missing capability: sp + "
                    f"comm-kernel hybrid projections)")
        if backend in ("ep", "ep_flash"):
            if not self.moe_family:
                raise ValueError(
                    f"backend={backend!r} routes the FFN through the EP "
                    "dispatch/combine kernels; "
                    f"{type(model).__name__} has no routed experts "
                    "(missing capability: expert-parallel FFN) — dense "
                    "models serve on 'flash'/'dist'/'ar'/'gemm_ar'")
            if getattr(model, "moe_impl", None) != "ep":
                raise ValueError(
                    f"backend={backend!r} needs an expert-SHARDED model "
                    f"(moe_impl='ep'); this Qwen3MoE was built "
                    f"moe_impl={model.moe_impl!r} — TP-MoE serves its "
                    "grouped-GEMM dispatch on 'flash' (or 'dist' for "
                    "the comm-kernel attention)")
        # where the model splits its LM head's vocabulary over a mesh
        # axis (DenseLLM.vocab_axis), every [B, V] logits array of the
        # slot programs is split the same way: the carry a scheduler
        # builds and re-arms is placed to logits_sharding, which is
        # what the model's head pins a tick's logits to
        from triton_dist_tpu.models.utils import last_axis_sharding
        vax = getattr(model, "vocab_axis", None)
        self.lm_head_shards = int(model.mesh.shape[vax]) if vax else 1
        # whether a decode step appends to the K and V pages as ONE
        # kv_append launch a layer (kernels/paged_kv.append_page_rows,
        # layers/tp_attn.py _attend_paged_slots): the pages the engine
        # moves itself, on a kernel backend; the int8 pool, the
        # sp-sharded pool and the xla oracle scatter
        self.kv_append_kernel = int(
            self._own is None and kv_dtype is None
            and self.sp_size == 1 and backend != "xla")
        self.logits_sharding = last_axis_sharding(model.mesh, 2, vax)
        # An expert-SHARDED model feeds row-sharded token batches to
        # the EP FFN (the a2a dispatch on the ep backends, the
        # all-gather oracle on the rest): every forward's row count
        # must divide by the ep axis, so the prefill pad buckets align
        # to lcm(8, ep) and max_seq (the bucket clamp) rounds up to it.
        ep = getattr(model, "ep_size", 1)
        self._ep_rows = 1
        if ep > 1:
            import math
            self._ep_rows = math.lcm(8, ep)
            self.max_seq = -(-self.max_seq // self._ep_rows) \
                * self._ep_rows
        self.sampling = sampling
        self._sample_params = dict(temperature=temperature, k=top_k,
                                   p=top_p)
        # process-global dispatch counters (runtime/telemetry.py): the
        # device-program mix every scheduler on this engine drives —
        # prefills vs decode vs verify vs mixed ticks — surfaced by
        # the TokenServer's /metrics listener next to each scheduler's
        # own registry. Cached Counter handles: inc() on the dispatch
        # path is one int add, no registry lock.
        _reg = default_registry()
        # ... and, beside them, jax's own trace / lower / compile
        # seconds by the role of the program dispatched
        install_compile_accounting()
        self._c_prefills = _reg.counter(
            "engine_prefill_dispatches", "prefill/admit forwards")
        self._c_decode = _reg.counter(
            "engine_decode_dispatches", "slot-scan decode chunks")
        self._c_verify = _reg.counter(
            "engine_verify_dispatches", "spec verify forwards")
        self._c_mixed = _reg.counter(
            "engine_mixed_dispatches", "mixed prefill+decode ticks")
        # TP comm-backend dispatch counter: every slot/verify/mixed
        # tick whose backend routes the projections through the
        # distributed comm kernels (AG-GEMM / GEMM-RS / AR / fused
        # GEMM+AR) counts here — the observable proof that multi-chip
        # serving actually exercises the paper's kernels (the TP=N
        # differential suite asserts it > 0). Complemented by
        # `comm_kernel_traces` (kernels/*) counting each comm kernel
        # BUILT into a program at trace time.
        self._c_comm = _reg.counter(
            "comm_kernel_dispatches", "slot-path dispatches through "
                                      "the dist/ar/gemm_ar backends")
        self._comm_backend = backend in ("dist", "ar", "gemm_ar")
        # int8-quantized models run on EVERY backend: the comm-kernel
        # GEMMs (ag_gemm/gemm_rs/gemm_allreduce) stream int8 weight
        # panels and dequant per column after the dot (exact), so the
        # bandwidth win survives multi-chip TP decode (reference analog:
        # quantized comm payloads, low_latency_all_to_all_v2.py:213).

        # the reference prefills with the torch fwd (engine.py:121); the
        # analog here is the XLA-collective mode unless overridden.
        # The ep backends prefill through THEMSELVES: chunked-prefill
        # differentials need the admit forward and the mixed tick on
        # one numerics path (the same reason "dist"/"flash" do).
        self.prefill_backend = prefill_backend or (
            backend if backend in ("dist", "flash", "ep", "ep_flash")
            else "xla")
        # MoE-family serving telemetry (ISSUE 13): every slot-tick
        # program additionally returns the tick's routing-load vector
        # [expert_tokens[0..E-1], capacity_dropped]; the engine stashes
        # the device array FIFO here and the scheduler's coalesced
        # readback (DecodeSlots._fetch) pops exactly one per landed
        # tick — no extra sync, and the overlap pipeline never blocks
        # on a still-in-flight tick's stats.
        import collections
        self._moe_pending = collections.deque()
        # The model is a jit ARGUMENT (weights must not be captured as
        # program constants — that would bake GBs into the executable).
        # The jitted program set is SHARED across Engine instances with
        # the same (backend, sampling, params, prefill mode) via a
        # process-wide factory (_jit_programs): jax's compile cache
        # keys on the python callable, so per-instance functools
        # partials used to recompile every executable once per engine
        # — a server fleet (or a test suite) building several engines
        # over the same model shapes paid the whole compile bill each
        # time. Sharing is safe because every per-engine mutable piece
        # (scratch caches, dispatch counters) stays on the instance and
        # the model rides in as a traced argument.
        progs = _jit_programs(backend, sampling,
                              _params_key(self._sample_params),
                              self.prefill_backend)
        # AOT WARM START (ISSUE 12 / ROADMAP item 5): with
        # TDTPU_AOT_CACHE=dir set, every serving program below is
        # wrapped by a disk cache of jax.export blobs keyed on
        # (backend, sampling, params, prefill mode, jax version, arg
        # shapes) — a restarted server (or an elastically added
        # worker) deserializes the lowered program instead of
        # retracing it, and the XLA executable comes out of the
        # persistent compilation cache pointed at the same directory
        # (tools/aot.py AOTProgramCache). Programs the host cannot
        # serialize (Pallas interpreter callbacks off-TPU) fall back
        # to their jit wrappers, counted in the cache stats.
        from triton_dist_tpu.tools.aot import wrap_serving_programs
        progs, self._aot = wrap_serving_programs(
            progs, context=(backend, sampling,
                            _params_key(self._sample_params),
                            self.prefill_backend))
        self._prefill = progs["prefill"]
        self._decode_scan = progs["decode_scan"]
        # slot-masked chunked decode (continuous batching,
        # models/scheduler.py) + the paged/verify/mixed program
        # family — all lazy-compiled on first use (the program
        # roles are documented on _jit_programs).
        self._slot_scan = progs["slot_scan"]
        self._prefill_slot = progs["prefill_slot"]
        self._write_slot = progs["write_slot"]
        # persistent 1-row scratch for prefill_into_slot, donated
        # through each admission instead of reallocated per request.
        # The scratch is engine-owned while caches are caller-owned,
        # so when several servers share one engine (fleet replicas),
        # concurrent admissions would donate the SAME scratch buffer
        # twice — the lock serializes the scratch-donating section
        # only (decode ticks touch caller-owned state and stay
        # lock-free).
        self._scratch_lock = threading.Lock()
        self._slot_scratch = None
        self._paged_slot_scan = progs["paged_slot_scan"]
        self._paged_admit = progs["paged_admit"]
        self._paged_set_table = progs["paged_set_table"]
        self._state_admit = progs["state_admit"]
        self._paged_scratch = None
        if sampling != "greedy":
            self._spec_seed = progs["spec_seed"]
        self._slot_verify = progs["slot_verify"]
        self._paged_slot_verify = progs["paged_slot_verify"]
        self._slot_mixed = progs["slot_mixed"]
        self._paged_slot_mixed = progs["paged_slot_mixed"]
        self._slot_mixed_verify = progs["slot_mixed_verify"]
        self._paged_slot_mixed_verify = \
            progs["paged_slot_mixed_verify"]
        self._paged_install = progs["paged_install"]
        self._gather_pages = progs["gather_pages"]
        self._restore_pages = progs["restore_pages"]

    def refuse_slot_state(self, option: str, capability: str) -> None:
        """Refuse `option` for a model whose slots hold state beside
        their pages (ServingTraits.slot_state) or whose pool only
        its own programs read (ServingTraits.own_pool): the option
        moves, shares or rebuilds a slot through the engine's K/V page
        programs, and `capability` names what would have to exist for
        it. A no-op for a model whose whole context is K and V pages."""
        if self.traits.slot_state:
            raise ValueError(
                f"{option}: {type(self.model).__name__} slots hold "
                f"{self.traits.slot_state} beside their pages (missing "
                f"capability: {capability})")
        if self.traits.own_pool:
            raise ValueError(
                f"{option}: {type(self.model).__name__} keeps "
                f"{self.traits.own_pool}, which the engine's K/V page "
                f"programs do not move (missing capability: "
                f"{capability})")

    def _contiguous_only(self, what: str) -> None:
        self.refuse_slot_state(
            what, f"contiguous cache over {self._own}; "
                  f"serve with ContinuousScheduler(paged=True) / "
                  f"TokenServer(paged=True)")

    def prefill(self, input_ids):
        """Run the prefill pass on a fresh cache; returns (logits, cache)."""
        self._contiguous_only("Engine.prefill / serve")
        input_ids = jnp.asarray(input_ids, dtype=jnp.int32)
        cache = self.model.make_cache(input_ids.shape[0], self.max_seq,
                                      dtype=self.kv_dtype)
        return self._prefill(self.model, input_ids, cache)

    def decode(self, logits, cache, gen_len: int, *, seed: int = 0):
        """Decode from prefill state: one jitted lax.scan over gen_len
        steps with a donated cache. Returns tokens [B, gen_len]. The
        benchmark times this call alone — it is the reference's measured
        decode loop (engine.py:166). `seed` feeds the sampler key for
        the non-greedy modes (ignored under greedy)."""
        if self.sampling == "greedy":
            toks, _, _ = self._decode_scan(self.model, logits, cache,
                                           gen_len=gen_len)
        else:
            toks, _, _, _ = self._decode_scan(
                self.model, logits, cache, jax.random.key(seed),
                gen_len=gen_len)
        return toks

    def serve(self, input_ids, gen_len: int, *, seed: int = 0):
        """Generate (reference: Engine.serve, engine.py:113).
        input_ids: [B, S] int32. Returns generated tokens [B, gen_len].
        """
        logits, cache = self.prefill(input_ids)
        return self.decode(logits, cache, gen_len, seed=seed)

    # ------------------------------------------------------------------
    # continuous-batching slot decode (models/scheduler.py drives these)
    # ------------------------------------------------------------------

    def _note_moe_load(self, out: tuple) -> tuple:
        """Strip + stash the routing-load vector every MoE-family slot
        program appends as its LAST output ([E+1] int32 device array:
        per-expert routed entries + capacity drops, summed over layers
        and scan steps). FIFO order matches tick dispatch order —
        scheduler._fetch pops one per landed tick and folds it into
        the expert_tokens/moe_capacity_drops/expert_load_imbalance
        metrics. Dense engines pass through untouched."""
        if not self.moe_family:
            return out
        self._moe_pending.append(out[-1])
        return out[:-1]

    def pop_moe_load(self):
        """The oldest undrained routing-load device array (or None).
        Callers must only pop a tick they are about to LAND (its
        outputs computed) — a device_get on it is then a plain d2h
        copy, never a pipeline stall."""
        if self.moe_family and self._moe_pending:
            return self._moe_pending.popleft()
        return None

    def _moe_batch_check(self, batch: int) -> None:
        """EP slot serving feeds [batch(*window), D] token rows to the
        row-sharded expert dispatch: refuse a scheduler batch the ep
        axis cannot split, at cache construction instead of as a
        shard_map divisibility error deep in the first tick."""
        ep = getattr(self.model, "ep_size", 1)
        if ep > 1 and batch % ep:
            raise ValueError(
                f"EP serving needs the slot batch ({batch}) divisible "
                f"by the expert-parallel axis size ({ep}): each tick "
                f"row-shards its token batch over the ep mesh axis "
                f"{self.model.ep_axis!r} — pad the batch or shrink "
                f"the ep axis")

    def make_slot_cache(self, batch: int):
        """Fresh cache whose batch rows are independent decode SLOTS."""
        self._contiguous_only("contiguous decode slots")
        if self.sp_size > 1:
            raise ValueError(
                "sequence-parallel serving shards the PAGE-ID space — "
                "contiguous slot caches have no pages to shard "
                "(missing capability: sp contiguous slots); construct "
                "ContinuousScheduler(paged=True) so the sp pool "
                "serves through the partial+LSE-combine attends")
        self._moe_batch_check(batch)
        return self.model.make_cache(batch, self.max_seq,
                                     dtype=self.kv_dtype)

    def prefill_into_slot(self, cache, slot, ids, *, pad_to: int = 8):
        """Prefill ONE new request and write its KV into batch row
        `slot` of the shared cache without touching live slots.

        The prompt runs as a batch-1 forward into a persistent 1-row
        scratch cache (allocated once per engine, donated through the
        jitted prefill each admission), padded up to a multiple of
        `pad_to` — clamped to max_seq — so the number of prefill
        programs is bounded by the bucket count, not the number of
        distinct prompt lengths (padded positions write garbage KV
        past the real length — never attended, because the slot's
        per-row length masks them, and overwritten as decode advances;
        the same masking makes scratch reuse across admissions safe).
        The scratch row is then copied over the slot's row — ONE
        dynamic-update-slice per layer buffer on the donated cache.
        Returns (next-token logits [V], cache).
        """
        ids = jnp.asarray(ids, jnp.int32).reshape(-1)
        n = ids.shape[0]
        if n > self.max_seq:
            raise ValueError(
                f"prompt length {n} exceeds slot capacity {self.max_seq}")
        self._c_prefills.inc()
        if self._ep_rows > 1:
            # EP models: the prefill's row count feeds the row-sharded
            # expert dispatch — buckets align to lcm(8, ep). max_seq
            # was rounded up to the same at __init__, so the clamp
            # below stays divisible.
            import math
            pad_to = math.lcm(pad_to, self._ep_rows)
        # the pad bucket must never write past the cache capacity
        # (max_seq need not be a pad_to multiple)
        P = min(-(-n // pad_to) * pad_to, self.max_seq)
        padded = jnp.zeros((1, P), jnp.int32).at[0, :n].set(ids)
        with self._scratch_lock:
            if self._slot_scratch is None:
                self._slot_scratch = self.model.make_cache(
                    1, self.max_seq, dtype=self.kv_dtype)
            logits, self._slot_scratch = self._prefill_slot(
                self.model, padded, self._slot_scratch,
                jnp.int32(n - 1))
            cache = self._write_slot(cache, self._slot_scratch,
                                     jnp.int32(slot))
        return logits[0], cache

    def slot_chunk(self, logits, cache, pos, active, *, chunk: int,
                   keys=None, mask=None):
        """One chunk of slot-masked decode: `chunk` scan steps where
        row b samples from its own logits, appends KV at its own
        pos[b], and advances only if active[b] (inactive slots write
        into their own dead rows — harmless, overwritten on admit).
        ONE XLA program per chunk length; admission/retirement happen
        between chunks on the host. keys: per-slot PRNG keys [B]
        (typed key array) for the sampled modes; None under greedy.
        Returns (toks [B, chunk], logits, cache, pos, keys).

        Dispatch contract (the overlap scheduler rides this): the call
        returns device FUTURES — the donated carry (logits/cache/pos/
        keys) can be fed straight into the next chunk's dispatch with
        NO host round-trip, and only reading `toks` blocks. The same
        holds for every slot program below (verify, mixed, paged):
        scheduler.DecodeSlots defers that read to one coalesced
        device_get per poll (_fetch), and overlap=True moves it past
        the next dispatch.

        mask: [B, V] bool grammar masks (models/structured.py) riding
        the existing operands — requires chunk == 1 (the mask is a
        scan constant); mask=None leaves every call expression
        byte-identical, so unconstrained serving never retraces."""
        if mask is not None and chunk != 1:
            raise ValueError(
                f"grammar masks are per-step (scan constants): serve "
                f"constrained slots at chunk == 1, got chunk={chunk}")
        self._c_decode.inc()
        if self._comm_backend:
            self._c_comm.inc()
        if self.sampling == "greedy":
            assert keys is None
            if mask is not None:
                toks, logits, cache, pos = self._note_moe_load(
                    self._slot_scan(self.model, logits, cache, pos,
                                    active, jnp.asarray(mask, bool),
                                    gen_len=chunk))
                return toks, logits, cache, pos, None
            toks, logits, cache, pos = self._note_moe_load(
                self._slot_scan(self.model, logits, cache, pos, active,
                                gen_len=chunk))
            return toks, logits, cache, pos, None
        if mask is not None:
            toks, logits, cache, pos, keys = self._note_moe_load(
                self._slot_scan(self.model, logits, cache, pos, active,
                                keys, jnp.asarray(mask, bool),
                                gen_len=chunk))
            return toks, logits, cache, pos, keys
        toks, logits, cache, pos, keys = self._note_moe_load(
            self._slot_scan(self.model, logits, cache, pos, active,
                            keys, gen_len=chunk))
        return toks, logits, cache, pos, keys


    # ------------------------------------------------------------------
    # speculative decoding (models/spec_decode.py policy; the
    # scheduler's spec=K mode drives these)
    # ------------------------------------------------------------------

    def spec_seed(self, row_logits, key, mask=None):
        """Draw the pending seed token for a freshly admitted slot from
        its prefill logits (sampled modes only; greedy admission takes
        the host argmax). mask [V] bool: grammar-legal support for a
        constrained slot. Returns (token, evolved key)."""
        assert self.sampling != "greedy"
        if mask is not None:
            return self._spec_seed(row_logits, key,
                                   jnp.asarray(mask, bool))
        return self._spec_seed(row_logits, key)

    def slot_verify_chunk(self, cache, pos, active, tokens, q_lens, *,
                          keys=None, mask=None):
        """One speculative verify step over the CONTIGUOUS slot cache:
        score every slot's draft window (tokens [B, S] — the pending
        seed token at column 0, up to S-1 drafts after, padded; q_lens
        [B] valid lengths) in ONE forward at per-slot positions pos,
        run the acceptance rule (greedy: longest argmax-matching
        prefix; sampled: leftover rejection sampling through the
        per-slot PRNG chains `keys`), write the window KV, and advance
        each slot by its accepted count — the rejected suffix stays as
        dead rows past the rewound length, overwritten by the next
        step. Returns (n_emit [B] — tokens kept from the window,
        t0_next [B] — the corrected next seed token, cache, pos, keys).
        mask: [B, S, V] bool per-position grammar masks
        (structured.window_masks) constraining acceptance + reseed.
        """
        tokens = jnp.asarray(tokens, jnp.int32)
        q_lens = jnp.asarray(q_lens, jnp.int32)
        self._c_verify.inc()
        if self._comm_backend:
            self._c_comm.inc()
        if self.sampling == "greedy":
            assert keys is None
            if mask is not None:
                n_emit, t0n, cache, pos = self._note_moe_load(
                    self._slot_verify(self.model, cache, pos, active,
                                      tokens, q_lens,
                                      jnp.asarray(mask, bool)))
                return n_emit, t0n, cache, pos, None
            n_emit, t0n, cache, pos = self._note_moe_load(
                self._slot_verify(self.model, cache, pos, active,
                                  tokens, q_lens))
            return n_emit, t0n, cache, pos, None
        if mask is not None:
            n_emit, t0n, cache, pos, keys = self._note_moe_load(
                self._slot_verify(self.model, cache, pos, active,
                                  tokens, q_lens, keys,
                                  jnp.asarray(mask, bool)))
            return n_emit, t0n, cache, pos, keys
        n_emit, t0n, cache, pos, keys = self._note_moe_load(
            self._slot_verify(self.model, cache, pos, active, tokens,
                              q_lens, keys))
        return n_emit, t0n, cache, pos, keys

    def paged_slot_verify_chunk(self, pcache, pos, active, tokens,
                                q_lens, *, keys=None, mask=None):
        """slot_verify_chunk over the PAGED pool: identical contract,
        with the window KV scatter and attention resolved through the
        page table (a padded row's write drops out of bounds, so it can
        never touch a live or cached page; rejected rows stay in the
        slot's own mapped pages until the next window overwrites them).
        """
        tokens = jnp.asarray(tokens, jnp.int32)
        q_lens = jnp.asarray(q_lens, jnp.int32)
        self._c_verify.inc()
        if self._comm_backend:
            self._c_comm.inc()
        if self.sampling == "greedy":
            assert keys is None
            if mask is not None:
                n_emit, t0n, pcache, pos = self._note_moe_load(
                    self._paged_slot_verify(self.model, pcache, pos,
                                            active, tokens, q_lens,
                                            jnp.asarray(mask, bool)))
                return n_emit, t0n, pcache, pos, None
            n_emit, t0n, pcache, pos = self._note_moe_load(
                self._paged_slot_verify(self.model, pcache, pos, active,
                                        tokens, q_lens))
            return n_emit, t0n, pcache, pos, None
        if mask is not None:
            n_emit, t0n, pcache, pos, keys = self._note_moe_load(
                self._paged_slot_verify(self.model, pcache, pos, active,
                                        tokens, q_lens, keys,
                                        jnp.asarray(mask, bool)))
            return n_emit, t0n, pcache, pos, keys
        n_emit, t0n, pcache, pos, keys = self._note_moe_load(
            self._paged_slot_verify(self.model, pcache, pos, active,
                                    tokens, q_lens, keys))
        return n_emit, t0n, pcache, pos, keys

    # ------------------------------------------------------------------
    # chunked prefill (Sarathi-Serve, 2403.02310 — PAPERS.md): the
    # scheduler's mixed prefill+decode ticks. One forward covers every
    # live decode slot (q_len = 1) AND up to prefill_budget tokens of
    # in-progress prefills (q_len = chunk), riding the verify paths'
    # per-slot q_lens/kv_lens masks: chunk rows write their KV
    # (contiguous columns or pages) exactly like a verify window, but
    # their "acceptance" is unconditional (they are prompt tokens) and
    # they emit a next-token logit only when the final chunk lands —
    # the slot then arms and joins decode (scheduler._arm_slot).
    # ------------------------------------------------------------------

    def slot_mixed_chunk(self, logits, cache, pos, active, prefilling,
                         tokens, q_lens, *, keys=None, mask=None):
        """One MIXED prefill+decode tick over the CONTIGUOUS slot cache.

        tokens [B, S] / q_lens [B]: row b of a PREFILLING slot holds
        its next q_lens[b] prompt tokens (positions pos[b] ..
        pos[b] + q_lens[b] - 1; q_lens[b] == 0 is a budget-starved
        prefill that makes no progress this tick); a decode row's
        column 0 is filled IN-PROGRAM from its own carry logits
        (argmax, or one per-slot key split — exactly one scan step of
        the plain chunk path) and q_lens[b] == 1. prefilling [B] bool
        marks the chunk rows (always disjoint from `active`: a
        prefilling slot is not armed). Returns (tok [B] — the token
        each decode row emitted this tick, sel_logits [B, V] — the
        logits at each row's last valid window position (a decode
        row's next carry; a final-chunk prefill row's ARMING logits),
        cache, pos, keys). pos advances by q_lens for prefill rows and
        by 1 for active decode rows. mask: [B, V] grammar masks over
        the decode rows' token selection (sel_logits stay raw)."""
        tokens = jnp.asarray(tokens, jnp.int32)
        q_lens = jnp.asarray(q_lens, jnp.int32)
        prefilling = jnp.asarray(prefilling, bool)
        if self.sampling == "greedy":
            assert keys is None
        self._c_mixed.inc()
        if self._comm_backend:
            self._c_comm.inc()
        if mask is not None:
            return self._note_moe_load(
                self._slot_mixed(self.model, logits, cache, pos, active,
                                 prefilling, tokens, q_lens, keys,
                                 jnp.asarray(mask, bool)))
        return self._note_moe_load(
            self._slot_mixed(self.model, logits, cache, pos, active,
                             prefilling, tokens, q_lens, keys))

    def paged_slot_mixed_chunk(self, logits, pcache, pos, active,
                               prefilling, tokens, q_lens, *, keys=None,
                               mask=None):
        """slot_mixed_chunk over the PAGED pool: identical contract,
        chunk rows scatter their KV through the page table (padded rows
        drop out of bounds) and attention walks the pool with per-slot
        kv_lens AND q_lens."""
        tokens = jnp.asarray(tokens, jnp.int32)
        q_lens = jnp.asarray(q_lens, jnp.int32)
        prefilling = jnp.asarray(prefilling, bool)
        if self.sampling == "greedy":
            assert keys is None
        self._c_mixed.inc()
        if self._comm_backend:
            self._c_comm.inc()
        if mask is not None:
            return self._note_moe_load(
                self._paged_slot_mixed(self.model, logits, pcache, pos,
                                       active, prefilling, tokens,
                                       q_lens, keys,
                                       jnp.asarray(mask, bool)))
        return self._note_moe_load(
            self._paged_slot_mixed(self.model, logits, pcache, pos,
                                   active, prefilling, tokens, q_lens,
                                   keys))

    def slot_mixed_verify_chunk(self, cache, pos, active, prefilling,
                                tokens, q_lens, *, keys=None, mask=None):
        """Spec-mode mixed tick (CONTIGUOUS): decode rows carry their
        draft-verify windows (seed at column 0, q_lens up to spec+1 —
        the _slot_verify contract) while prefill rows carry prompt
        chunks; ONE forward scores everything. The acceptance epilogue
        runs for decode rows only; prefill rows advance by their full
        chunk unconditionally. Returns (n_emit [B], t0_next [B],
        sel_logits [B, V] — arming logits at each row's last valid
        window position, cache, pos, keys). mask: [B, S, V] grammar
        window masks over acceptance (sel_logits stay raw)."""
        tokens = jnp.asarray(tokens, jnp.int32)
        q_lens = jnp.asarray(q_lens, jnp.int32)
        prefilling = jnp.asarray(prefilling, bool)
        if self.sampling == "greedy":
            assert keys is None
        self._c_mixed.inc()
        if self._comm_backend:
            self._c_comm.inc()
        if mask is not None:
            return self._note_moe_load(
                self._slot_mixed_verify(self.model, cache, pos, active,
                                        prefilling, tokens, q_lens,
                                        keys, jnp.asarray(mask, bool)))
        return self._note_moe_load(
            self._slot_mixed_verify(self.model, cache, pos, active,
                                    prefilling, tokens, q_lens, keys))

    def paged_slot_mixed_verify_chunk(self, pcache, pos, active,
                                      prefilling, tokens, q_lens, *,
                                      keys=None, mask=None):
        """slot_mixed_verify_chunk over the PAGED pool."""
        tokens = jnp.asarray(tokens, jnp.int32)
        q_lens = jnp.asarray(q_lens, jnp.int32)
        prefilling = jnp.asarray(prefilling, bool)
        if self.sampling == "greedy":
            assert keys is None
        self._c_mixed.inc()
        if self._comm_backend:
            self._c_comm.inc()
        if mask is not None:
            return self._note_moe_load(
                self._paged_slot_mixed_verify(self.model, pcache, pos,
                                              active, prefilling,
                                              tokens, q_lens, keys,
                                              jnp.asarray(mask, bool)))
        return self._note_moe_load(
            self._paged_slot_mixed_verify(self.model, pcache, pos,
                                          active, prefilling, tokens,
                                          q_lens, keys))

    def install_slot_paged(self, pcache, slot: int, rows, cow_src,
                           cow_dst, cow_rows: int):
        """Chunk 0 of a CHUNKED paged admission: install the slot's
        table row and copy-on-write the partially matched
        boundary page — the one-time half of admit_slot_paged, with the
        suffix prefill left to the mixed-chunk ticks (which resolve
        their KV scatter and attention through the table just
        installed). Same rows/cow contract as admit_slot_paged."""
        self.refuse_slot_state(
            "install_slot_paged (chunked admission, KV fork)",
            "a table install that also carries the state")
        return self._paged_install(
            self.model, pcache, jnp.asarray(rows, jnp.int32),
            jnp.int32(slot), jnp.asarray(cow_src, jnp.int32),
            jnp.asarray(cow_dst, jnp.int32), jnp.int32(cow_rows))

    # ------------------------------------------------------------------
    # paged slot path (shared-prefix serving; models/prefix_cache.py
    # owns the policy — radix tree, refcounts, eviction — and drives
    # these device-side entry points through the scheduler).
    #
    # The slot lifecycle these programs implement is PREEMPTIBLE
    # (models/scheduler.py resilience): a preemption is exactly a
    # retire (retire_slot_paged — tree insert is host bookkeeping,
    # table rows to trash) followed later by a re-admission of the
    # prompt + generated sequence through admit_slot_paged, whose
    # prefix match caps at n-1 so only the last token's KV recomputes
    # while the tree still holds the pages. No preemption-specific
    # device program exists — that is the point.
    # ------------------------------------------------------------------

    def make_paged_slot_cache(self, batch: int, *, page: int = 16,
                              num_pages: Optional[int] = None,
                              for_ticks: bool = True):
        """Paged slot cache: per-layer physical pools behind ONE shared
        page table (kv_cache.PagedSlotCache). num_pages defaults to the
        no-sharing worst case (every slot full) + the reserved trash
        page; pass fewer to let prefix sharing carry the load (and the
        LRU evictor handle the pressure).

        kv_dtype=int8 engines get the INT8 POOL (per-position scale
        planes riding the page payload — kv_cache.PagedSlotCache):
        half the decode KV read, double the resident pages, streams
        bitwise equal to the contiguous int8 cache.

        TP: the pool's page payloads are HEAD-SHARDED over the model's
        mesh (kv_cache.PagedSlotCache TP SHARDING) and the slot
        programs run each chip's attention over its local kv-head
        shard under shard_map — one scheduler drives the whole TP=N
        mesh. The mesh size must divide n_kv_heads (validated here
        with a real error instead of a shard shape mismatch deep in
        compile); GQA replication (num_heads > num_kv_heads) is a
        query-side property and changes nothing about the pool split."""
        if not hasattr(self.model, "forward_tokens_slots_paged"):
            raise ValueError(
                f"{type(self.model).__name__} has no paged slot decode "
                "path (DenseLLM, Qwen3MoE, Phi4Flash, DeepSeekV3, Afmoe "
                "and FalconH1 carry the serving surface)")
        if for_ticks:
            # a pool that will DRIVE decode/verify/mixed ticks feeds
            # its batch rows to the row-sharded EP dispatch; staging
            # pools (disagg prefill workers, for_ticks=False) only run
            # bucketed admit forwards and skip the batch gate
            self._moe_batch_check(batch)
        cfg = self.model.config
        Hkv = self.traits.kv_heads
        tp = self.model.mesh.shape[self.model.axis]
        if Hkv % tp:
            rep = cfg.num_heads // max(Hkv, 1)
            raise ValueError(
                f"paged TP serving needs num_kv_heads "
                f"({Hkv}) divisible by the TP mesh size "
                f"({tp}); this model's GQA replication factor is {rep} "
                f"(query heads replicate per kv head, but the KV pool "
                f"itself splits on kv heads) — serve on a mesh whose "
                f"size divides {Hkv}, or replicate kv "
                f"heads in the checkpoint")
        maxp = -(-self.max_seq // page)
        sp_ax = getattr(self.model, "sp_axis", None)
        if num_pages is None:
            num_pages = batch * maxp + 1
            if self.sp_size > 1:
                # the default rounds UP to the sp partition (each chip
                # owns a whole contiguous id block)
                num_pages = -(-num_pages // self.sp_size) * self.sp_size
        elif self.sp_size > 1 and num_pages % self.sp_size:
            raise ValueError(
                f"sequence-parallel pool needs num_pages ({num_pages}) "
                f"divisible by the sp mesh size ({self.sp_size}): the "
                f"page-id space partitions into equal per-chip blocks "
                f"— round num_pages up to a multiple of {self.sp_size} "
                f"or shrink the sp axis")
        # the model lays out its own cache: one pool a layer for the
        # Qwen families, pages + rings + planes for a hybrid
        return self.model.make_paged_cache(
            batch, self.max_seq, page=page, num_pages=num_pages,
            dtype=self.kv_dtype,
            sp_axis=sp_ax if self.sp_size > 1 else None)

    def admit_slot_paged(self, pcache, slot: int, ids, rows,
                         kv_start: int, cow_src, cow_dst, cow_rows: int,
                         *, pad_to: int = 8):
        """Admit one request into paged slot `slot`, reusing a cached
        prefix of `kv_start` tokens (prefill-from-offset: ONLY the
        n - kv_start uncached suffix tokens are computed, bucketed to
        `pad_to` like prefill_into_slot).

        rows: [max_pages] int32 — the slot's full table row (shared
        prefix pages + fresh writable pages, trash-padded).
        cow_src/cow_dst: page ids for the copy-on-write of a
        partially-matched boundary page (cow_rows valid rows of every
        head are copied src -> dst before anything reads the slot's
        table; pass the trash page for both when kv_start is
        page-aligned).

        Returns (next-token logits [V], pcache). One XLA program per
        suffix bucket; kv_start/slot/cow are traced data.
        """
        ids = jnp.asarray(ids, jnp.int32).reshape(-1)
        if self._ep_rows > 1:
            # suffix buckets feed the row-sharded expert dispatch too
            import math
            pad_to = math.lcm(pad_to, self._ep_rows)
        n = int(ids.shape[0])
        m = int(kv_start)
        if not 0 <= m < n:
            raise ValueError(f"kv_start {m} out of range for prompt {n}"
                             " (the last token is always recomputed)")
        T_pool = pcache.capacity
        if n > T_pool:
            raise ValueError(
                f"prompt length {n} exceeds slot capacity {T_pool}")
        s = n - m
        P = -(-s // pad_to) * pad_to
        padded = jnp.zeros((1, P), jnp.int32).at[0, :s].set(ids[m:])
        self._c_prefills.inc()
        if self._own:
            # the model's own admission program: pages (and rings and
            # planes) of the slot in one pass, no scratch, no prefix
            if m:
                raise ValueError(
                    f"kv_start={m}: {type(self.model).__name__} admits "
                    f"a whole prompt through its own program (missing "
                    f"capability: prefix reuse over {self._own})")
            logits, pcache = self._state_admit(
                self.model, padded, pcache, jnp.asarray(rows, jnp.int32),
                jnp.int32(slot), jnp.int32(n))
            return logits[0], pcache
        with self._scratch_lock:
            scr = self._paged_scratch
            if scr is None or scr.k[0].shape[2] != T_pool + pad_to:
                # scratch holds [prefix | suffix bucket]; the + pad_to
                # tail keeps the bucketed DUS in range at every
                # kv_start
                self._paged_scratch = self.model.make_cache(
                    1, T_pool + pad_to, dtype=self.kv_dtype)
            logits, self._paged_scratch, pcache = self._paged_admit(
                self.model, padded, self._paged_scratch, pcache,
                jnp.asarray(rows, jnp.int32), jnp.int32(slot),
                jnp.int32(m), jnp.int32(n),
                jnp.asarray(cow_src, jnp.int32),
                jnp.asarray(cow_dst, jnp.int32), jnp.int32(cow_rows))
        return logits[0], pcache

    def paged_slot_chunk(self, logits, pcache, pos, active, *,
                         chunk: int, keys=None, mask=None):
        """slot_chunk over the paged pool: identical contract, but each
        row's KV scatter resolves through the page table (a retired
        row's table maps the trash page, so its masked-out writes can
        never touch a live or cached page).

        mask: [B, V] grammar masks (chunk == 1 required, see
        slot_chunk)."""
        self._c_decode.inc()
        if self._comm_backend:
            self._c_comm.inc()
        if mask is not None and chunk != 1:
            raise ValueError(
                f"grammar masks are per-step (scan constants): serve "
                f"constrained slots at chunk == 1, got chunk={chunk}")
        if self.sampling == "greedy":
            assert keys is None
            if mask is not None:
                toks, logits, pcache, pos = self._note_moe_load(
                    self._paged_slot_scan(self.model, logits, pcache,
                                          pos, active,
                                          jnp.asarray(mask, bool),
                                          gen_len=chunk))
                return toks, logits, pcache, pos, None
            toks, logits, pcache, pos = self._note_moe_load(
                self._paged_slot_scan(self.model, logits, pcache, pos,
                                      active, gen_len=chunk))
            return toks, logits, pcache, pos, None
        if mask is not None:
            toks, logits, pcache, pos, keys = self._note_moe_load(
                self._paged_slot_scan(self.model, logits, pcache, pos,
                                      active, keys,
                                      jnp.asarray(mask, bool),
                                      gen_len=chunk))
            return toks, logits, pcache, pos, keys
        toks, logits, pcache, pos, keys = self._note_moe_load(
            self._paged_slot_scan(self.model, logits, pcache, pos,
                                  active, keys, gen_len=chunk))
        return toks, logits, pcache, pos, keys

    def retire_slot_paged(self, pcache, slot: int):
        """Point the whole table row of a retired slot at the trash
        page (the write sink): the slot scan keeps stepping masked
        rows, and their scatters must never land on a page the
        allocator may have handed to someone else."""
        row = jnp.full((pcache.table.shape[1],), pcache.trash, jnp.int32)
        return self._paged_set_table(pcache, row, jnp.int32(slot))

    # ------------------------------------------------------------------
    # host KV tier (models/kv_tier.py): demote/promote page spans
    # between the device pools and pinned host RAM. The prefix cache's
    # residency machine (models/prefix_cache.py) drives these through
    # the PagedDecodeSlots callbacks.
    # ------------------------------------------------------------------

    def extract_pages_host(self, pcache, page_ids, *, pad_to: int = 8):
        """DEMOTION d2h (also the disaggregated-serving WIRE FORMAT —
        models/disagg.py ships exactly these arrays from the prefill
        plane's staging pool to the decode pool, a transferred page
        being a demoted page with a different destination): gather the
        listed physical pages out of every
        layer's K/V pool and return them as host arrays
        (k, v each [L, N, Hkv, page, d], pool dtype — the raw bytes of
        every head, so a later restore is bitwise, whatever the mesh
        the pool's heads are sharded over; an int8 pool appends its
        scale planes (k, v, ks, vs) so the scales ride the same
        transfer). The id list is trash-padded to a pad_to bucket
        (bounded executable count; the padded reads are sliced off
        before returning). The gather is dispatched async — the
        device_get below is the synchronization point, i.e. the copy
        overlaps whatever was already in flight."""
        import numpy as np
        ids = np.asarray(page_ids, np.int32).reshape(-1)
        n = len(ids)
        P = max(-(-n // pad_to) * pad_to, pad_to)
        padded = np.full((P,), pcache.trash, np.int32)
        padded[:n] = ids
        out = self._gather_pages(self.model, pcache, jnp.asarray(padded))
        # one device_get over every array: the K/V (and scale) d2h
        # transfers overlap instead of serializing on the eviction
        # critical path
        out = jax.device_get(out)
        return tuple(np.asarray(a)[:, :n].copy() for a in out)

    def restore_pages_host(self, pcache, page_ids, host_k, host_v,
                           host_ks=None, host_vs=None, *,
                           pad_to: int = 8):
        """PROMOTION h2d: install previously extracted page contents
        (extract_pages_host's k/v arrays — plus its ks/vs scale planes
        for an int8 pool) into the listed freshly allocated physical
        pages of every layer's pool — one scatter program per bucket
        on the donated cache, run BEFORE the promoted prefix is mapped
        into any slot's table. Padded tail ids point at the trash page
        (zero payload — harmless)."""
        import numpy as np
        ids = np.asarray(page_ids, np.int32).reshape(-1)
        n = len(ids)
        if host_k.shape[1] != n or host_v.shape[1] != n:
            raise ValueError(
                f"payload covers {host_k.shape[1]} pages, ids list "
                f"{n}")
        if bool(pcache.scales_k) != (host_ks is not None):
            raise ValueError(
                "int8 pools restore payloads WITH scale planes; bf16 "
                "pools without — the payload does not match this pool")
        P = max(-(-n // pad_to) * pad_to, pad_to)
        padded = np.full((P,), pcache.trash, np.int32)
        padded[:n] = ids
        L = host_k.shape[0]
        hk = np.zeros((L, P) + host_k.shape[2:], host_k.dtype)
        hv = np.zeros((L, P) + host_v.shape[2:], host_v.dtype)
        hk[:, :n] = host_k
        hv[:, :n] = host_v
        hsk = hsv = None
        if host_ks is not None:
            hsk = np.zeros((L, P) + host_ks.shape[2:], host_ks.dtype)
            hsv = np.zeros((L, P) + host_vs.shape[2:], host_vs.dtype)
            hsk[:, :n] = host_ks
            hsv[:, :n] = host_vs
            hsk, hsv = jnp.asarray(hsk), jnp.asarray(hsv)
        return self._restore_pages(self.model, pcache,
                                   jnp.asarray(padded),
                                   jnp.asarray(hk), jnp.asarray(hv),
                                   hsk, hsv)


def _params_key(params: dict) -> tuple:
    """Hashable key of the sampling params dict (the _jit_programs
    cache key component)."""
    return (params["temperature"], params["k"], params["p"])


@functools.lru_cache(maxsize=None)
def _jit_programs(backend: str, sampling: str, pkey: tuple,
                  prefill_mode: str) -> dict:
    """The engine's jitted program set, ONE per (backend, sampling,
    params, prefill-mode) configuration process-wide.

    jax's executable cache keys on the python callable object, so
    building these per Engine instance (the old per-__init__ partials)
    recompiled every program once per engine — serving restarts, test
    suites, and TP-vs-single-chip differentials all paid the whole
    compile bill repeatedly for identical configurations. The model is
    a traced ARGUMENT of every program (weights never bake in), and
    all mutable per-engine state (scratch caches, counters) lives on
    the instance, so sharing the jit wrappers is purely a
    compile-cache win. Contents:

    - prefill / decode_scan: the uniform-batch serve() pair;
    - slot_scan / prefill_slot / write_slot: continuous batching
      (models/scheduler.py) — slot-masked chunked decode + the
      bucketed prefill-into-slot pair;
    - paged_slot_scan / paged_admit / paged_set_table /
      paged_install: the shared-prefix paged pool family (admission =
      table install + CoW + prefix gather + suffix
      prefill-from-offset + KV scatter; retire = table reset);
    - slot_verify / paged_slot_verify (+ spec_seed under sampling):
      speculative-decoding verify forwards with the on-device accept;
    - slot_mixed / paged_slot_mixed (+ _verify twins): the chunked-
      prefill mixed prefill+decode ticks;
    - gather_pages / restore_pages: the host-KV-tier d2h/h2d pair.

    MODEL FAMILIES (ISSUE 13): the same jit wrappers serve the dense
    AND the `moe` model family — the model rides in as a traced
    argument and its static config picks the trace (_is_moe), so a
    Qwen3MoE compiles slot programs that run per-slot top-k routing +
    grouped-GEMM expert dispatch inside every tick and append the
    routing-load vector as one extra output (Engine._note_moe_load
    strips and stashes it), while dense models' traces stay
    byte-identical. ep/ep_flash backends (expert-sharded FFN over the
    a2a kernels) flow through the same program set as a mode string.

    All lazy-compiled: a path never exercised costs nothing."""
    params = dict(temperature=pkey[0], k=pkey[1], p=pkey[2])
    greedy = sampling == "greedy"
    P = {}
    P["prefill"] = jax.jit(functools.partial(_prefill_fn,
                                             mode=prefill_mode))
    scan_fn = (functools.partial(_scan_decode_fn, backend) if greedy
               else functools.partial(_sampled_scan_decode_fn,
                                      backend, sampling, params))
    P["decode_scan"] = jax.jit(scan_fn, static_argnames=("gen_len",),
                               donate_argnums=(2,))
    slot_fn = (functools.partial(_slot_scan_decode_fn, backend)
               if greedy else
               functools.partial(_sampled_slot_scan_decode_fn, backend,
                                 sampling, params))
    P["slot_scan"] = jax.jit(slot_fn, static_argnames=("gen_len",),
                             donate_argnums=(2,))
    P["prefill_slot"] = jax.jit(
        functools.partial(_prefill_slot_fn, mode=prefill_mode),
        donate_argnums=(2,))
    P["write_slot"] = jax.jit(_write_slot_fn, donate_argnums=(0,))
    paged_fn = (functools.partial(_paged_slot_scan_decode_fn, backend)
                if greedy else
                functools.partial(_sampled_paged_slot_scan_fn, backend,
                                  sampling, params))
    P["paged_slot_scan"] = jax.jit(paged_fn,
                                   static_argnames=("gen_len",),
                                   donate_argnums=(2,))
    P["paged_admit"] = jax.jit(
        functools.partial(_paged_admit_fn, mode=prefill_mode),
        donate_argnums=(2, 3))
    P["paged_set_table"] = jax.jit(_paged_set_table_fn,
                                   donate_argnums=(0,))
    P["state_admit"] = jax.jit(
        functools.partial(_state_admit_fn, mode=prefill_mode),
        donate_argnums=(2,))
    if greedy:
        vfn = functools.partial(_slot_verify_fn, backend)
        pvfn = functools.partial(_paged_slot_verify_fn, backend)
    else:
        vfn = functools.partial(_sampled_slot_verify_fn, backend,
                                sampling, params)
        pvfn = functools.partial(_sampled_paged_slot_verify_fn, backend,
                                 sampling, params)
        P["spec_seed"] = jax.jit(functools.partial(_spec_seed_fn,
                                                   sampling, params))
    P["slot_verify"] = jax.jit(vfn, donate_argnums=(1,))
    P["paged_slot_verify"] = jax.jit(pvfn, donate_argnums=(1,))
    samp = None if greedy else sampling
    P["slot_mixed"] = jax.jit(
        functools.partial(_mixed_step_fn, backend, samp, params, False),
        donate_argnums=(2,))
    P["paged_slot_mixed"] = jax.jit(
        functools.partial(_mixed_step_fn, backend, samp, params, True),
        donate_argnums=(2,))
    P["slot_mixed_verify"] = jax.jit(
        functools.partial(_mixed_verify_fn, backend, samp, params,
                          False),
        donate_argnums=(1,))
    P["paged_slot_mixed_verify"] = jax.jit(
        functools.partial(_mixed_verify_fn, backend, samp, params,
                          True),
        donate_argnums=(1,))
    P["paged_install"] = jax.jit(_paged_install_fn, donate_argnums=(1,))
    P["gather_pages"] = jax.jit(_gather_pages_fn)
    P["restore_pages"] = jax.jit(_restore_pages_fn, donate_argnums=(1,))
    # each known by its role to the compile accounting (runtime/
    # telemetry.py): what a dispatch traces, lowers and compiles is
    # counted under this name. The jitted callables are untouched: they
    # stay the partials they are, so a trace still shows jit__unknown
    register_program_roles(P)
    return P


def _prefill_fn(model, ids, cache, *, mode):
    return model.forward_tokens(ids, cache, mode=mode)


def _prefill_slot_fn(model, ids, cache, last_pos, *, mode):
    """Bucketed batch-1 prefill: logits taken at the last REAL prompt
    position (the pad tail's logits are garbage and discarded). The
    scratch cache is REUSED across admissions (donated through), so its
    offset must restart at 0 every time."""
    import dataclasses
    cache = dataclasses.replace(cache, offset=jnp.int32(0))
    return model.forward_tokens(ids, cache, mode=mode, last_pos=last_pos)


def _write_slot_fn(cache, scratch, slot):
    """Copy a 1-row scratch cache over batch row `slot` of the shared
    slot cache (donated): one DUS per layer buffer. The whole row is
    replaced — including the zero tail — so stale KV from a retired
    request cannot leak into the new occupant's masked-out columns."""
    import dataclasses

    def put(bufs, rows):
        return tuple(
            jax.lax.dynamic_update_slice(
                b, r.astype(b.dtype), (slot,) + (0,) * (b.ndim - 1))
            for b, r in zip(bufs, rows))

    out = dataclasses.replace(
        cache, k=put(cache.k, scratch.k), v=put(cache.v, scratch.v))
    if cache.ks:
        out = dataclasses.replace(out, ks=put(cache.ks, scratch.ks),
                                  vs=put(cache.vs, scratch.vs))
    return out


def _is_moe(model) -> bool:
    """Static (trace-time) family switch of the slot programs below:
    a MoE-family model's slot forwards additionally return the tick's
    routing-load vector (the `moe` model family of _jit_programs —
    same jit wrappers, the model's static config picks the trace).
    config is static pytree metadata, so this never retraces a given
    model inconsistently."""
    return bool(getattr(model.config, "is_moe", False)) \
        and hasattr(model, "_zero_load")


def _slot_scan_decode_fn(backend, model, logits0, cache, pos, active,
                         mask=None, *, gen_len: int):
    """Slot-masked greedy decode chunk (continuous batching): same
    shape as _scan_decode_fn, but each batch row is an independent
    request at its own position. Inactive rows still flow through the
    program (masking keeps it ONE executable for every occupancy mix);
    their writes land in their own dead cache rows and their tokens are
    discarded by the scheduler. MoE family: the routing-load vector
    rides the scan carry and returns as one extra output (the dense
    trace is untouched).

    mask [B, V] bool (models/structured.py grammar masks): token
    selection argmaxes over where(mask, logits, -inf) — constant
    across the scan, so grammar serving runs chunk == 1 (the
    scheduler's _eff_chunk); mask=None leaves the trace byte-identical
    to before the grammar subsystem existed."""
    act = active.astype(jnp.int32)
    moe = _is_moe(model)

    def step(carry, _):
        if moe:
            logits, cache, pos, load = carry
        else:
            logits, cache, pos = carry
        sel = logits if mask is None else \
            jnp.where(mask, logits, -jnp.inf)
        tok = jnp.argmax(sel, axis=-1)              # greedy [B]
        tok = jnp.where(active, tok, 0)
        if moe:
            logits, cache, st = model.forward_tokens_slots(
                tok[:, None], cache, pos, mode=backend,
                return_moe_stats=True)
        else:
            logits, cache = model.forward_tokens_slots(
                tok[:, None], cache, pos, mode=backend)
        # clamp: a slot that finished mid-chunk keeps stepping until the
        # chunk boundary; its surplus writes stay inside its own row
        pos = jnp.minimum(pos + act, cache.k[0].shape[2] - 1)
        if moe:
            return (logits, cache, pos, load + st), tok
        return (logits, cache, pos), tok

    init = ((logits0, cache, pos, model._zero_load()) if moe
            else (logits0, cache, pos))
    out, toks = jax.lax.scan(step, init, None, length=gen_len)
    if moe:
        logits, cache, pos, load = out
        return toks.T, logits, cache, pos, load      # [B, gen_len]
    logits, cache, pos = out
    return toks.T, logits, cache, pos                # [B, gen_len]


def _sampled_slot_scan_decode_fn(backend, sampling, params, model,
                                 logits0, cache, pos, active, keys,
                                 mask=None, *, gen_len: int):
    """Sampled slot decode chunk: per-slot PRNG keys split once per
    step, so each slot's sampled chain equals a single-request
    Engine.serve() at that slot's seed — and is invariant to chunk
    boundaries and to whatever the other slots are doing.

    mask [B, V] bool: grammar-illegal logits drop to -inf BEFORE the
    top-k/top-p sampler, so the emitted marginal is the sampler's
    renormalized over the legal support (mask=None: untouched trace)."""
    from triton_dist_tpu.models.utils import sample_top_k, sample_top_p

    temp = max(params["temperature"], 0.0)
    act = active.astype(jnp.int32)

    def sample_one(k, logits):
        if temp == 0.0:
            return jnp.argmax(logits, axis=-1)
        if sampling == "top_k":
            return sample_top_k(k, logits, k=params["k"],
                                temperature=temp)
        return sample_top_p(k, logits, p=params["p"], temperature=temp)

    moe = _is_moe(model)

    def step(carry, _):
        if moe:
            logits, cache, pos, keys, load = carry
        else:
            logits, cache, pos, keys = carry
        split = jax.vmap(functools.partial(jax.random.split, num=2))
        ks = split(keys)
        keys, subs = ks[:, 0], ks[:, 1]
        sel = logits if mask is None else \
            jnp.where(mask, logits, -jnp.inf)
        tok = jax.vmap(sample_one)(subs, sel)       # [B]
        tok = jnp.where(active, tok, 0)
        if moe:
            logits, cache, st = model.forward_tokens_slots(
                tok[:, None], cache, pos, mode=backend,
                return_moe_stats=True)
        else:
            logits, cache = model.forward_tokens_slots(
                tok[:, None], cache, pos, mode=backend)
        pos = jnp.minimum(pos + act, cache.k[0].shape[2] - 1)
        if moe:
            return (logits, cache, pos, keys, load + st), tok
        return (logits, cache, pos, keys), tok

    init = ((logits0, cache, pos, keys, model._zero_load()) if moe
            else (logits0, cache, pos, keys))
    out, toks = jax.lax.scan(step, init, None, length=gen_len)
    if moe:
        logits, cache, pos, keys, load = out
        return toks.T, logits, cache, pos, keys, load
    logits, cache, pos, keys = out
    return toks.T, logits, cache, pos, keys          # [B, gen_len]


def _spec_seed_fn(sampling, params, logits, key, mask=None):
    """Sample the pending seed token for a fresh spec-mode slot from
    its prefill logits, consuming one split of the slot's PRNG chain
    (models/spec_decode.py; greedy admission argmaxes on the host).
    mask [V] bool: grammar-legal support for a constrained slot's
    arming draw (None: untouched trace)."""
    from triton_dist_tpu.models.utils import sample_top_k, sample_top_p
    temp = max(params["temperature"], 0.0)
    key, sub = jax.random.split(key)
    if mask is not None:
        logits = jnp.where(mask, logits, -jnp.inf)
    if temp == 0.0:
        tok = jnp.argmax(logits, axis=-1)
    elif sampling == "top_k":
        tok = sample_top_k(sub, logits, k=params["k"], temperature=temp)
    else:
        tok = sample_top_p(sub, logits, p=params["p"], temperature=temp)
    return tok.astype(jnp.int32), key


def _verify_accept(sampling, params, logits_all, tokens, q_lens, active,
                   pos, cap, keys=None):
    """Shared acceptance epilogue of the four verify programs
    (models/spec_decode.py): greedy = longest argmax-matching prefix +
    corrected token; sampled = leftover rejection sampling through the
    per-slot PRNG chains (emitted marginal equals the spec-off
    sampler's at every position; temperature=0 collapses to greedy,
    mirroring the samplers' degeneracy). Inactive slots report
    n_emit == 0; pos advances by the accepted count, clamped to the
    cache capacity — the rejected suffix stays as dead rows past the
    rewound length. Returns (n_emit, t0_next, pos, keys)."""
    from triton_dist_tpu.models.spec_decode import (accept_greedy,
                                                    accept_sampled,
                                                    target_probs)
    if sampling is None or max(params["temperature"], 0.0) == 0.0:
        nxt = jnp.argmax(logits_all, axis=-1).astype(jnp.int32)
        n_emit, t0n = accept_greedy(tokens, nxt, q_lens)
    else:
        probs = target_probs(logits_all, sampling, params)
        n_emit, t0n, keys = accept_sampled(keys, probs, tokens, q_lens)
    n_emit = n_emit * active.astype(jnp.int32)
    pos = jnp.minimum(pos + n_emit, cap - 1)
    return n_emit, t0n, pos, keys


def _verify_forward(backend, paged, model, cache, pos, tokens, q_lens):
    """The verify-window forward shared by the verify AND mixed
    programs (contiguous or paged), MoE-family aware: returns
    (per-position logits [B, S, V], cache, capacity, load) where load
    is the routing-load vector for MoE models and None for dense —
    the dense traces are byte-identical to before the MoE family
    existed."""
    moe = _is_moe(model)
    if paged:
        if moe:
            logits_all, cache, load = \
                model.forward_tokens_slots_paged_verify(
                    tokens, cache, pos, q_lens, mode=backend,
                    return_moe_stats=True)
        else:
            logits_all, cache = model.forward_tokens_slots_paged_verify(
                tokens, cache, pos, q_lens, mode=backend)
            load = None
        return logits_all, cache, cache.capacity, load
    if moe:
        logits_all, cache, load = model.forward_tokens_slots_verify(
            tokens, cache, pos, q_lens, mode=backend,
            return_moe_stats=True)
    else:
        logits_all, cache = model.forward_tokens_slots_verify(
            tokens, cache, pos, q_lens, mode=backend)
        load = None
    return logits_all, cache, cache.k[0].shape[2], load


def _slot_verify_fn(backend, model, cache, pos, active, tokens, q_lens,
                    mask=None):
    """Greedy speculative verify (contiguous cache): one forward over
    every slot's padded draft window + the shared on-device acceptance
    epilogue (_verify_accept). Inactive slots flow through masked
    (q_lens handed in as 1, writes land in their own dead rows).

    mask [B, S, V] bool (structured.window_masks): the acceptance rule
    — argmax matching and the corrected seed — runs over
    where(mask, logits, -inf), so a grammar slot only ever accepts or
    reseeds grammar-legal tokens; None = byte-identical trace."""
    logits_all, cache, cap, load = _verify_forward(
        backend, False, model, cache, pos, tokens, q_lens)
    acc = logits_all if mask is None else \
        jnp.where(mask, logits_all, -jnp.inf)
    n_emit, t0n, pos, _ = _verify_accept(
        None, None, acc, tokens, q_lens, active, pos, cap)
    if load is not None:
        return n_emit, t0n, cache, pos, load
    return n_emit, t0n, cache, pos


def _sampled_slot_verify_fn(backend, sampling, params, model, cache, pos,
                            active, tokens, q_lens, keys, mask=None):
    """Sampled _slot_verify_fn: leftover rejection sampling through the
    per-slot PRNG chains (see _verify_accept); a grammar mask zeroes
    the illegal tokens' target probabilities before acceptance."""
    logits_all, cache, cap, load = _verify_forward(
        backend, False, model, cache, pos, tokens, q_lens)
    acc = logits_all if mask is None else \
        jnp.where(mask, logits_all, -jnp.inf)
    n_emit, t0n, pos, keys = _verify_accept(
        sampling, params, acc, tokens, q_lens, active, pos,
        cap, keys)
    if load is not None:
        return n_emit, t0n, cache, pos, keys, load
    return n_emit, t0n, cache, pos, keys


def _paged_slot_verify_fn(backend, model, pcache, pos, active, tokens,
                          q_lens, mask=None):
    """_slot_verify_fn over the PAGED pool (the prefix-cache serving
    path): identical acceptance, KV resolved through the page table."""
    logits_all, pcache, cap, load = _verify_forward(
        backend, True, model, pcache, pos, tokens, q_lens)
    acc = logits_all if mask is None else \
        jnp.where(mask, logits_all, -jnp.inf)
    n_emit, t0n, pos, _ = _verify_accept(
        None, None, acc, tokens, q_lens, active, pos, cap)
    if load is not None:
        return n_emit, t0n, pcache, pos, load
    return n_emit, t0n, pcache, pos


def _sampled_paged_slot_verify_fn(backend, sampling, params, model,
                                  pcache, pos, active, tokens, q_lens,
                                  keys, mask=None):
    """Sampled _paged_slot_verify_fn (see _verify_accept)."""
    logits_all, pcache, cap, load = _verify_forward(
        backend, True, model, pcache, pos, tokens, q_lens)
    acc = logits_all if mask is None else \
        jnp.where(mask, logits_all, -jnp.inf)
    n_emit, t0n, pos, keys = _verify_accept(
        sampling, params, acc, tokens, q_lens, active, pos,
        cap, keys)
    if load is not None:
        return n_emit, t0n, pcache, pos, keys, load
    return n_emit, t0n, pcache, pos, keys


def _mixed_step_fn(backend, sampling, params, paged, model, logits0,
                   cache, pos, active, prefilling, tokens, q_lens, keys,
                   mask=None):
    """Non-spec MIXED prefill+decode tick (chunked prefill,
    models/scheduler.py step_mixed): decode rows behave as exactly one
    step of the plain slot scan (sample from the carry logits — one key
    split per row under the sampled modes, same chain as
    _sampled_slot_scan_decode_fn — write KV at pos, advance 1); prefill
    rows feed their prompt chunk through the verify-window machinery
    (KV written at pos .. pos + q_len - 1, attention over the kv_len
    prior tokens + causal within the window) and advance by q_len. The
    returned sel_logits take each row's LAST valid window position:
    a decode row's next carry, a final-chunk prefill row's arming
    logits (non-final chunks return live-but-unused logits the
    scheduler overwrites on the next tick). A budget-starved prefill
    row (q_len == 0) writes nothing (its padded rows scatter out of
    bounds) and advances 0.

    mask [B, V] bool: constrains the decode rows' token selection from
    the carry logits only — sel_logits stay RAW (a prefill row's
    arming logits must be the unconstrained model output; the grammar
    mask applies at every SELECTION from them, never to the carry)."""
    from triton_dist_tpu.models.utils import (sample_top_k, sample_top_p,
                                              split_last_axis)
    B, S = tokens.shape
    sel0 = logits0 if mask is None else \
        jnp.where(mask, logits0, -jnp.inf)
    if sampling is None or max(params["temperature"], 0.0) == 0.0:
        tok = jnp.argmax(sel0, axis=-1).astype(jnp.int32)
    else:
        temp = max(params["temperature"], 0.0)

        def sample_one(k, logits):
            if sampling == "top_k":
                return sample_top_k(k, logits, k=params["k"],
                                    temperature=temp)
            return sample_top_p(k, logits, p=params["p"],
                                temperature=temp)

        split = jax.vmap(functools.partial(jax.random.split, num=2))
        ks = split(keys)
        keys, subs = ks[:, 0], ks[:, 1]
        tok = jax.vmap(sample_one)(subs, sel0).astype(jnp.int32)
    tok = jnp.where(active, tok, 0)
    toks = tokens.at[:, 0].set(jnp.where(active, tok, tokens[:, 0]))
    logits_all, cache, cap, load = _verify_forward(
        backend, paged, model, cache, pos, toks, q_lens)
    sel = jnp.maximum(q_lens - 1, 0)
    # [B, V], placed as the scheduler's carry is (logits_sharding)
    sel_logits = split_last_axis(logits_all[jnp.arange(B), sel],
                                 model.mesh,
                                 getattr(model, "vocab_axis", None))
    adv = jnp.where(prefilling, q_lens, active.astype(jnp.int32))
    pos = jnp.minimum(pos + adv, cap - 1)
    if load is not None:
        return tok, sel_logits, cache, pos, keys, load
    return tok, sel_logits, cache, pos, keys


def _mixed_verify_fn(backend, sampling, params, paged, model, cache, pos,
                     active, prefilling, tokens, q_lens, keys,
                     mask=None):
    """Spec-mode mixed tick: one verify-shaped forward over decode
    draft windows AND prefill chunks; the acceptance epilogue
    (_verify_accept) applies to decode rows only (n_emit masked by
    `active`, which is False for prefilling slots), then prefill rows
    advance unconditionally by their chunk length. sel_logits are the
    per-row last-valid-position logits (the arming logits when a final
    chunk lands). mask [B, S, V]: acceptance only — sel_logits stay
    RAW (see _mixed_step_fn)."""
    from triton_dist_tpu.models.utils import split_last_axis
    B, S = tokens.shape
    logits_all, cache, cap, load = _verify_forward(
        backend, paged, model, cache, pos, tokens, q_lens)
    acc = logits_all if mask is None else \
        jnp.where(mask, logits_all, -jnp.inf)
    n_emit, t0n, pos, keys = _verify_accept(
        sampling, params, acc, tokens, q_lens, active, pos, cap,
        keys)
    pos = jnp.minimum(pos + jnp.where(prefilling, q_lens, 0), cap - 1)
    sel = jnp.maximum(q_lens - 1, 0)
    # [B, V], placed as the scheduler's carry is (logits_sharding)
    sel_logits = split_last_axis(logits_all[jnp.arange(B), sel],
                                 model.mesh,
                                 getattr(model, "vocab_axis", None))
    if load is not None:
        return n_emit, t0n, sel_logits, cache, pos, keys, load
    return n_emit, t0n, sel_logits, cache, pos, keys


def _page_rows(g):
    """Gathered pages [n, h, page(, d)] -> each head's contiguous rows
    [h, n*page(, d)]: what a contiguous scratch cache holds."""
    g = jnp.swapaxes(g, 0, 1)
    return g.reshape((g.shape[0], -1) + g.shape[3:])


def _pool_gather_heads(mesh, axis, pool, row):
    """Head-aligned pool gather (the admit program's prefix read on
    the TP-sharded pool): row [maxp] page ids -> the mapped pages'
    bytes [Hkv, maxp*page(, d)], each rank reading its OWN kv heads of
    the [NP, Hkv, page(, d)] pool. Comm-free by construction — the
    output is head-sharded exactly like the contiguous scratch it
    fills."""
    from jax.sharding import PartitionSpec as P
    if pool.ndim == 4:
        in_p, out_p = P(None, axis, None, None), P(axis, None, None)
    else:
        in_p, out_p = P(None, axis, None), P(axis, None)

    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=(in_p, P(None)), out_specs=out_p,
                       check_vma=False)
    def f(p_loc, row_):
        return _page_rows(p_loc[row_])   # [maxp, h_loc, page(, d)]

    return f(pool, row)


def _pool_scatter_heads(mesh, axis, pool, dest, ri, u):
    """Head-aligned pool scatter (the admit program's suffix
    write-back): u [Hkv, S(, d)] — a head-sharded scratch slice — lands
    at (dest [S] page ids, ri [S] in-page rows) of each rank's own
    heads of the [NP, Hkv, page(, d)] pool. Trash dest ids are the
    sanctioned sink (pad-bucket tail rows)."""
    from jax.sharding import PartitionSpec as P
    if pool.ndim == 4:
        in_p, u_p = P(None, axis, None, None), P(axis, None, None)
    else:
        in_p, u_p = P(None, axis, None), P(axis, None)

    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=(in_p, P(None), P(None), u_p),
                       out_specs=in_p, check_vma=False)
    def f(p_loc, dest_, ri_, u_loc):
        return set_page_rows(p_loc, dest_, ri_, jnp.swapaxes(u_loc, 0, 1))

    return f(pool, dest, ri, u)


def _sp_owned_local(ids, pps, me, *, oob=None):
    """THE sp page-id partition rule, one copy (mirrored device-side
    by layers/tp_attn._attend_paged_slots_sp): global page id p lives
    on shard p // pps in contiguous blocks. Returns (owned mask,
    local ids) — for GATHERS (oob=None) non-owned ids clamp in range
    (their values are masked to zero before the psum); for SCATTERS
    (oob=<local pool size>) they redirect out of range so the write
    drops."""
    owned = (ids // pps) == me
    if oob is None:
        loc = jnp.clip(ids - me * pps, 0, pps - 1)
    else:
        loc = jnp.where(owned, ids - me * pps, oob)
    return owned, loc


def _sp_specs(sp_axis, pool):
    from jax.sharding import PartitionSpec as P
    tail = (None,) * (pool.ndim - 1)
    return P(sp_axis, *tail), P(None, *tail)


def _pool_gather_sp(mesh, sp_axis, pool, ids):
    """Page gather on the SP-sharded pool (the admit program's prefix
    read, the demotion's d2h — kv_cache.PagedSlotCache SP SHARDING):
    ids [n] GLOBAL page ids -> the pages' bytes [n, Hkv, page(, d)]
    REPLICATED over sp. Each chip reads the pages it owns (others
    contribute zeros) and one psum assembles the full span — traffic
    is exactly the gathered bytes, never the pool (floats sum x+0+...
    exactly, so the assembly is bitwise)."""
    from jax.sharding import PartitionSpec as P
    in_p, out_p = _sp_specs(sp_axis, pool)

    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=(in_p, P(None)), out_specs=out_p,
                       check_vma=False)
    def f(p_loc, ids_):
        pps = p_loc.shape[0]
        me = jax.lax.axis_index(sp_axis)
        owned, loc = _sp_owned_local(ids_, pps, me)
        g = p_loc[loc]                   # [n, Hkv, page(, d)]
        mask = owned.reshape(owned.shape + (1,) * (g.ndim - 1))
        g = jnp.where(mask, g, 0).astype(p_loc.dtype)
        return jax.lax.psum(g, sp_axis)

    return f(pool, ids)


def _pool_scatter_sp(mesh, sp_axis, pool, dest, ri, u):
    """Page-row scatter on the SP-sharded pool (the admit program's
    suffix write-back): u [Hkv, S(, d)] replicated rows land at
    (dest [S] GLOBAL page ids, ri [S] in-page rows). Each chip
    writes ONLY the pages it owns — non-owned (and deliberately
    out-of-range) destinations redirect past the local shard and the
    scatter drops them, so the write is comm-free. Global trash ids
    land in shard 0's local trash page, the sanctioned sink."""
    from jax.sharding import PartitionSpec as P
    in_p, _ = _sp_specs(sp_axis, pool)
    u_p = P(*(None,) * u.ndim)

    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=(in_p, P(None), P(None), u_p),
                       out_specs=in_p, check_vma=False)
    def f(p_loc, dest_, ri_, u_loc):
        pps = p_loc.shape[0]
        me = jax.lax.axis_index(sp_axis)
        _, loc = _sp_owned_local(dest_, pps, me, oob=pps)
        return set_page_rows(p_loc, loc, ri_, jnp.swapaxes(u_loc, 0, 1))

    return f(pool, dest, ri, u)


def _pool_put_sp(mesh, sp_axis, pool, ids, h):
    """Whole-page install on the SP-sharded pool (the promotion's
    h2d): h [n, Hkv, page(, d)] replicated lands in pages ids [n]
    (GLOBAL); each chip keeps the pages it owns, the rest drop."""
    from jax.sharding import PartitionSpec as P
    in_p, h_p = _sp_specs(sp_axis, pool)

    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=(in_p, P(None), h_p), out_specs=in_p,
                       check_vma=False)
    def f(p_loc, ids_, h_):
        pps = p_loc.shape[0]
        me = jax.lax.axis_index(sp_axis)
        _, loc = _sp_owned_local(ids_, pps, me, oob=pps)
        return p_loc.at[loc].set(h_.astype(p_loc.dtype))

    return f(pool, ids, h)


def _cow_page(mesh, sp_axis, pool, cow_src, cow_dst, cow_r):
    """Boundary-page copy-on-write: rows [0, cow_r) of page cow_src,
    every head, copy into page cow_dst (the slot's own fresh page,
    which then receives the request's diverging writes). cow_r == 0
    (a page-aligned match) writes nothing. On the head-sharded pool a
    whole-page copy stays on its chip. On the SP pool (sp_axis set)
    src and dst may live on DIFFERENT shards (the allocator rotates
    fresh pages), so the copy is one owned-page gather (+psum) and
    one owned-page row scatter whose rows past cow_r redirect out of
    range."""
    page = pool.shape[2]
    live = jnp.arange(page) < cow_r
    if sp_axis is not None:
        src = _pool_gather_sp(mesh, sp_axis, pool, cow_src[None])[0]
        dest = jnp.where(live, cow_dst, pool.shape[0])   # OOB = no-op
        return _pool_scatter_sp(mesh, sp_axis, pool, dest,
                                jnp.arange(page), src)
    mask = live.reshape((1, page) + (1,) * (pool.ndim - 3))
    return pool.at[cow_dst].set(
        jnp.where(mask, pool[cow_src], pool[cow_dst]))


def _paged_install_fn(model, pcache, row, slot, cow_src, cow_dst,
                      cow_r):
    """Table install + boundary-page copy-on-write for a CHUNKED paged
    admission (chunk 0): exactly the pre-forward half of
    _paged_admit_fn. The CoW must happen before ANY chunk forward reads
    the slot's table (_cow_page). An int8 pool copies the boundary
    page's scale rows alongside.

    `model` rides in ONLY for the mesh/sp_axis statics (its weights
    are dead arguments XLA prunes): a Mesh cannot live on the cache as
    static aux — the AOT exporter JSON-encodes pytree auxdata
    (tools/aot.py), and Mesh has no JSON form — so the three
    cache-movement programs (install/gather/restore) take the model
    like every other serving program does."""
    import dataclasses
    sp_ax = getattr(model, "sp_axis", None) if pcache.sp > 1 else None
    table = jax.lax.dynamic_update_slice(pcache.table, row[None],
                                         (slot, 0))

    def cow(pools):
        return tuple(_cow_page(model.mesh, sp_ax, p, cow_src, cow_dst,
                               cow_r) for p in pools)

    return dataclasses.replace(
        pcache, pages_k=cow(pcache.pages_k), pages_v=cow(pcache.pages_v),
        scales_k=cow(pcache.scales_k), scales_v=cow(pcache.scales_v),
        table=table)


def _paged_admit_fn(model, ids, scratch, pcache, row, slot, m, n,
                    cow_src, cow_dst, cow_r, *, mode):
    """Paged admission program (one per suffix bucket): install the
    slot's table row, copy-on-write the partially-matched boundary
    page, gather the slot's mapped pages into the contiguous scratch,
    run the suffix forward from offset m (the prefill-from-offset —
    positions [m, n) only), and scatter the computed suffix KV back
    into the slot's writable pages (pad-bucket tail rows are redirected
    to the trash page).

    INT8 pool: the scale planes ride every hop — boundary-page CoW
    copies the scale rows with the payload rows, the gather fills the
    int8 scratch's ks/vs (so the suffix forward attends the prefix
    through the contiguous int8 dequant path), and the suffix scatter
    writes the scales the forward's quantizer produced back beside the
    payload. The scratch is an int8 KVCache whenever the pool is (both
    derive from engine.kv_dtype), so the two branches can never be
    mismatched.

    TP pool ([NP, Hkv, page, d] head-sharded): the prefix gather and
    the suffix scatter run HEAD-ALIGNED under shard_map
    (_pool_gather_heads / _pool_scatter_heads) — each rank moves its
    own kv heads' bytes between its shard of the pool and its shard of
    the (head-sharded) contiguous scratch, so the whole admission
    stays ONE sharded program with zero cross-chip page traffic.

    SP pool (model.sp_axis — the page-id space sharded over sp,
    kv_cache.PagedSlotCache SP SHARDING): the prefix gather assembles
    each chip's owned pages with one psum (_pool_gather_sp — traffic
    is the gathered span, never the pool), the suffix forward runs on
    the replicated contiguous scratch, and the suffix scatter is
    comm-free (each chip keeps only the rows of pages it owns,
    _pool_scatter_sp); the boundary CoW crosses shards as a gather +
    scatter (the allocator rotates pages, so src and dst need not be
    co-resident)."""
    import dataclasses
    page = pcache.page
    maxp = row.shape[0]
    Hkv, d = pcache.kv_heads, pcache.pages_k[0].shape[3]
    mesh, axis = model.mesh, model.axis
    sp_ax = getattr(model, "sp_axis", None) if pcache.sp > 1 else None
    quant = bool(pcache.scales_k)
    table = jax.lax.dynamic_update_slice(pcache.table, row[None],
                                         (slot, 0))
    S_pad = ids.shape[1]
    p = m + jnp.arange(S_pad)
    valid = p < n
    pi = jnp.minimum(p // page, maxp - 1)
    ri = p % page
    dest = jnp.where(valid, row[pi], pcache.trash)           # [S_pad]

    def cow(pool):
        return _cow_page(mesh, sp_ax, pool, cow_src, cow_dst, cow_r)

    def gather(pool):
        if sp_ax is not None:
            return _page_rows(_pool_gather_sp(mesh, sp_ax, pool, row))
        return _pool_gather_heads(mesh, axis, pool, row)

    def scatter(pool, u):
        if sp_ax is not None:
            return _pool_scatter_sp(mesh, sp_ax, pool, dest, ri, u)
        return _pool_scatter_heads(mesh, axis, pool, dest, ri, u)

    pk, pv = list(pcache.pages_k), list(pcache.pages_v)
    psk, psv = list(pcache.scales_k), list(pcache.scales_v)
    sk, sv = list(scratch.k), list(scratch.v)
    ssk, ssv = list(scratch.ks), list(scratch.vs)
    for li in range(len(pk)):
        pk[li] = cow(pk[li])
        pv[li] = cow(pv[li])
        kf = gather(pk[li])[None]
        vf = gather(pv[li])[None]
        sk[li] = jax.lax.dynamic_update_slice(
            sk[li], kf.astype(sk[li].dtype), (0, 0, 0, 0))
        sv[li] = jax.lax.dynamic_update_slice(
            sv[li], vf.astype(sv[li].dtype), (0, 0, 0, 0))
        if quant:
            psk[li] = cow(psk[li])
            psv[li] = cow(psv[li])
            ksf = gather(psk[li])[None]
            vsf = gather(psv[li])[None]
            ssk[li] = jax.lax.dynamic_update_slice(ssk[li], ksf,
                                                   (0, 0, 0))
            ssv[li] = jax.lax.dynamic_update_slice(ssv[li], vsf,
                                                   (0, 0, 0))
    scratch = dataclasses.replace(scratch, k=tuple(sk), v=tuple(sv),
                                  ks=tuple(ssk), vs=tuple(ssv),
                                  offset=m)
    logits, scratch = model.forward_tokens(ids, scratch, mode=mode,
                                           last_pos=(n - 1) - m)
    pk2, pv2, psk2, psv2 = [], [], [], []
    for li in range(len(pk)):
        ks = jax.lax.dynamic_slice(scratch.k[li], (0, 0, m, 0),
                                   (1, Hkv, S_pad, d))[0]
        vs = jax.lax.dynamic_slice(scratch.v[li], (0, 0, m, 0),
                                   (1, Hkv, S_pad, d))[0]
        pk2.append(scatter(pk[li], ks))
        pv2.append(scatter(pv[li], vs))
        if quant:
            kss = jax.lax.dynamic_slice(scratch.ks[li], (0, 0, m),
                                        (1, Hkv, S_pad))[0]
            vss = jax.lax.dynamic_slice(scratch.vs[li], (0, 0, m),
                                        (1, Hkv, S_pad))[0]
            psk2.append(scatter(psk[li], kss))
            psv2.append(scatter(psv[li], vss))
    pcache = dataclasses.replace(pcache, pages_k=tuple(pk2),
                                 pages_v=tuple(pv2),
                                 scales_k=tuple(psk2),
                                 scales_v=tuple(psv2), table=table)
    return logits, scratch, pcache


def _paged_set_table_fn(pcache, row, slot):
    """Retire: the slot's table row to `row` (the trash page), and
    whatever else the cache keeps for a slot cleared (clear_slot: a
    no-op for a cache that is pages alone)."""
    import dataclasses
    table = jax.lax.dynamic_update_slice(pcache.table, row[None],
                                         (slot, 0))
    return dataclasses.replace(pcache, table=table).clear_slot(slot)


def _state_admit_fn(model, ids, pcache, rows, slot, n, *, mode):
    """Admission of a model whose slots hold state beside pages
    (ServingTraits.slot_state): the model's own program."""
    return model.admit_slot_paged(ids, pcache, rows, slot, n, mode=mode)


def _gather_pages_fn(model, pcache, ids):
    """Host-tier demotion gather: the listed pages of every layer's
    pool, stacked [L, N, Hkv, page, d] (one program per id-bucket
    shape). An int8 pool also gathers the scale planes
    [L, N, Hkv, page] — a demoted page's scales are part of its bytes.
    A gather moves bytes — no arithmetic — so the d2h/h2d round trip
    stays bitwise whatever the mesh: on the TP pool each chip supplies
    its own heads of every page.

    SP pool: a demoted span's pages live on S different chips (the
    allocator rotates pages), so ONE span is assembled from S
    per-chip contributions — each chip supplies the pages it owns and
    a psum puts the span together (_pool_gather_sp's rule: x + 0 + ..
    is exact, the round trip stays bitwise)."""
    if pcache.sp > 1:
        def pick(p):
            return _pool_gather_sp(model.mesh, model.sp_axis, p, ids)
    else:
        def pick(p):
            return p[ids]

    k = jnp.stack([pick(p) for p in pcache.pages_k])
    v = jnp.stack([pick(p) for p in pcache.pages_v])
    if pcache.scales_k:
        sk = jnp.stack([pick(s) for s in pcache.scales_k])
        sv = jnp.stack([pick(s) for s in pcache.scales_v])
        return k, v, sk, sv
    return k, v


def _restore_pages_fn(model, pcache, ids, hk, hv, hsk=None, hsv=None):
    """Host-tier promotion scatter: write hk/hv [L, N, Hkv, page, d]
    into the listed pages of every layer's pool (donated). Padded tail
    ids all point at the trash page — duplicate scatter targets there
    are fine, trash content is never read. Int8 pools restore the
    scale planes from hsk/hsv [L, N, Hkv, page] in the same program.

    SP pool: each chip keeps only the pages it owns (non-owned ids
    redirect out of local range and drop) — a restored span scatters
    back onto its S chips comm-free, the inverse of the gather."""
    import dataclasses
    if pcache.sp > 1:
        def put(p, h):
            return _pool_put_sp(model.mesh, model.sp_axis, p, ids, h)
    else:
        def put(p, h):
            return p.at[ids].set(h.astype(p.dtype))

    pk = tuple(put(p, hk[li]) for li, p in enumerate(pcache.pages_k))
    pv = tuple(put(p, hv[li]) for li, p in enumerate(pcache.pages_v))
    out = dataclasses.replace(pcache, pages_k=pk, pages_v=pv)
    if pcache.scales_k:
        psk = tuple(put(s, hsk[li])
                    for li, s in enumerate(pcache.scales_k))
        psv = tuple(put(s, hsv[li])
                    for li, s in enumerate(pcache.scales_v))
        out = dataclasses.replace(out, scales_k=psk, scales_v=psv)
    return out


def _paged_slot_scan_decode_fn(backend, model, logits0, pcache, pos,
                               active, mask=None, *, gen_len: int):
    """Greedy slot-masked decode chunk over the PAGED pool: same shape
    as _slot_scan_decode_fn with the per-row KV scatter and attention
    resolved through the page table (and the same [B, V] grammar-mask
    contract)."""
    act = active.astype(jnp.int32)
    cap = pcache.capacity
    moe = _is_moe(model)

    def step(carry, _):
        if moe:
            logits, pc, pos, load = carry
        else:
            logits, pc, pos = carry
        sel = logits if mask is None else \
            jnp.where(mask, logits, -jnp.inf)
        tok = jnp.argmax(sel, axis=-1)
        tok = jnp.where(active, tok, 0)
        if moe:
            logits, pc, st = model.forward_tokens_slots_paged(
                tok[:, None], pc, pos, mode=backend,
                return_moe_stats=True)
        else:
            logits, pc = model.forward_tokens_slots_paged(
                tok[:, None], pc, pos, mode=backend)
        pos = jnp.minimum(pos + act, cap - 1)
        if moe:
            return (logits, pc, pos, load + st), tok
        return (logits, pc, pos), tok

    init = ((logits0, pcache, pos, model._zero_load()) if moe
            else (logits0, pcache, pos))
    out, toks = jax.lax.scan(step, init, None, length=gen_len)
    if moe:
        logits, pcache, pos, load = out
        return toks.T, logits, pcache, pos, load      # [B, gen_len]
    logits, pcache, pos = out
    return toks.T, logits, pcache, pos                # [B, gen_len]


def _sampled_paged_slot_scan_fn(backend, sampling, params, model,
                                logits0, pcache, pos, active, keys,
                                mask=None, *, gen_len: int):
    """Sampled paged slot chunk: per-slot PRNG chains exactly as in
    _sampled_slot_scan_decode_fn — the sampler never sees the cache
    layout, so paged streams equal contiguous streams token for token
    whenever the logits do."""
    from triton_dist_tpu.models.utils import sample_top_k, sample_top_p

    temp = max(params["temperature"], 0.0)
    act = active.astype(jnp.int32)
    cap = pcache.capacity

    def sample_one(k, logits):
        if temp == 0.0:
            return jnp.argmax(logits, axis=-1)
        if sampling == "top_k":
            return sample_top_k(k, logits, k=params["k"],
                                temperature=temp)
        return sample_top_p(k, logits, p=params["p"], temperature=temp)

    moe = _is_moe(model)

    def step(carry, _):
        if moe:
            logits, pc, pos, keys, load = carry
        else:
            logits, pc, pos, keys = carry
        split = jax.vmap(functools.partial(jax.random.split, num=2))
        ks = split(keys)
        keys, subs = ks[:, 0], ks[:, 1]
        sel = logits if mask is None else \
            jnp.where(mask, logits, -jnp.inf)
        tok = jax.vmap(sample_one)(subs, sel)
        tok = jnp.where(active, tok, 0)
        if moe:
            logits, pc, st = model.forward_tokens_slots_paged(
                tok[:, None], pc, pos, mode=backend,
                return_moe_stats=True)
        else:
            logits, pc = model.forward_tokens_slots_paged(
                tok[:, None], pc, pos, mode=backend)
        pos = jnp.minimum(pos + act, cap - 1)
        if moe:
            return (logits, pc, pos, keys, load + st), tok
        return (logits, pc, pos, keys), tok

    init = ((logits0, pcache, pos, keys, model._zero_load()) if moe
            else (logits0, pcache, pos, keys))
    out, toks = jax.lax.scan(step, init, None, length=gen_len)
    if moe:
        logits, pcache, pos, keys, load = out
        return toks.T, logits, pcache, pos, keys, load
    logits, pcache, pos, keys = out
    return toks.T, logits, pcache, pos, keys          # [B, gen_len]


def _scan_decode_fn(backend, model, logits0, cache, *, gen_len: int):
    # NOTE: the logits carry is deliberate — a tok-only carry measured
    # ~3% SLOWER on-chip (XLA schedules the argmax off the critical
    # path this way)
    def step(carry, _):
        logits, cache = carry
        tok = jnp.argmax(logits, axis=-1)           # greedy [B]
        logits, cache = model.forward_tokens(tok[:, None], cache,
                                             mode=backend)
        return (logits, cache), tok

    (logits, cache), toks = jax.lax.scan(
        step, (logits0, cache), None, length=gen_len)
    return toks.T, logits, cache                     # [B, gen_len]


def _sampled_scan_decode_fn(backend, sampling, params, model, logits0,
                            cache, key, *, gen_len: int):
    """Sampled decode scan: same structure as _scan_decode_fn with a
    PRNG key in the carry, split once per step (reference: the sampling
    loop of the chat server, model_server.py + models/utils.py).
    temperature=0 degenerates to argmax so servers can flip modes
    without recompiling a separate greedy engine. The evolved key is
    RETURNED so chunked callers (serving.decode_stream) continue the
    exact chain — a resumed scan samples the same tokens as one long
    scan at the same seed."""
    from triton_dist_tpu.models.utils import sample_top_k, sample_top_p

    temp = max(params["temperature"], 0.0)

    def sample(k, logits):
        if temp == 0.0:
            return jnp.argmax(logits, axis=-1)
        if sampling == "top_k":
            return sample_top_k(k, logits, k=params["k"],
                                temperature=temp)
        return sample_top_p(k, logits, p=params["p"], temperature=temp)

    def step(carry, _):
        logits, cache, key = carry
        key, sub = jax.random.split(key)
        tok = sample(sub, logits)                   # [B]
        logits, cache = model.forward_tokens(tok[:, None], cache,
                                             mode=backend)
        return (logits, cache, key), tok

    (logits, cache, key), toks = jax.lax.scan(
        step, (logits0, cache, key), None, length=gen_len)
    return toks.T, logits, cache, key                # [B, gen_len]
