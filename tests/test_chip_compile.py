"""The main serving path's Pallas kernels, compiled by the TPU's own
compiler for a DESCRIBED v5e chip at Qwen3-1.7B widths.

No chip is attached and nothing runs: these catch what interpret mode
cannot — a slice not aligned to the tiling, a kernel over the VMEM
limit, a shape Mosaic refuses — at no chip time. Shapes are the ones
`chip_smoke.py` drives (B=8, Hq=16, Hkv=8, d=128, max_seq 512, page 16,
FFN 6144, bf16). The `phi4_` cases are Phi-4-mini-flash's new kernels and
walks at its published widths (models/phi4flash.py), the `fh_` cases
Falcon-H1's SSD kernels at its own (kernels/ssd.py).

The topology is described inside a module-scoped fixture and nowhere at
import: the TPU library admits one process at a time, xdist workers all
import this file, and only the worker that RUNS it may load the library.
Keep every such test in this one file.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

B, HQ, HKV, D, T, PAGE = 8, 16, 8, 128, 512, 16
HIDDEN, FFN = 2048, 6144
MAXP = T // PAGE
NUM_PAGES = B * MAXP + 1
BF16, I32 = jnp.bfloat16, jnp.int32


def _machine_cpus() -> int:
    with open("/proc/cpuinfo") as f:
        return sum(line.startswith("processor") for line in f)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        if "fakecpus" in os.environ.get("LD_PRELOAD", ""):
            # conftest's CPU-substrate shim inflates the CPU count libc
            # reports; the TPU library checks that count against the
            # machine's topology as it loads and ABORTS the process on
            # a mismatch. The shim reads FAKE_NPROC on every call, so
            # tell the truth for as long as the load takes.
            mp.setenv("FAKE_NPROC", str(_machine_cpus()))
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    return Mesh(np.array(topo.devices), ("tp",))


@pytest.fixture()
def for_chip(monkeypatch):
    """Kernels take their compiled (not interpreted) form, and the
    persistent compile cache stays out of it: an entry written for a
    described device cannot be read back without one, and would warn."""
    from jax.experimental.compilation_cache import compilation_cache
    from triton_dist_tpu.runtime import bootstrap
    monkeypatch.setattr(bootstrap, "on_tpu", lambda: True)
    assert bootstrap.interpret_mode() is False
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _flash_decode(q, k, v, kv_len):
    from triton_dist_tpu.kernels.flash_attn import flash_decode
    return flash_decode(q, k, v, kv_len)


def _flash_decode_slots(q, k, v, kv_lens):
    from triton_dist_tpu.kernels.flash_attn import flash_decode
    return flash_decode(q, k, v, jnp.max(kv_lens), kv_lens=kv_lens)


def _flash_decode_paged(q, pk, pv, table, kv_lens):
    from triton_dist_tpu.kernels.paged_kv import flash_decode_paged
    return flash_decode_paged(q, pk, pv, table, jnp.max(kv_lens),
                              kv_lens=kv_lens)


def _flash_decode_paged_windows(q, pk, pv, table, kv_lens, q_lens):
    from triton_dist_tpu.kernels.paged_kv import flash_decode_paged
    return flash_decode_paged(q, pk, pv, table, jnp.max(kv_lens),
                              kv_lens=kv_lens, q_lens=q_lens)


def _kv_update(cache, new, tile_pos):
    from triton_dist_tpu.kernels.flash_attn import kv_update
    return kv_update(cache, new, tile_pos)


def _swiglu(x2):
    from triton_dist_tpu.kernels.swiglu import swiglu
    return swiglu(x2)


def _q(b, s):
    return ((b, s, HQ, D), BF16)


def _kv(b, t):
    return ((b, HKV, t, D), BF16)


_POOL = ((NUM_PAGES, HKV, PAGE, D), BF16)   # a page: all heads of a slot


def _paged_cell(hkv, b=32, max_seq=2048, s=1):
    """flash_decode_paged's arguments for a chip that holds `hkv` KV
    heads of every slot (and the 16 query heads over them): its shard
    [NP, hkv, page, d] of the pool, one table row a slot; s > 1 adds
    the per-slot query windows."""
    maxp = max_seq // PAGE
    pool = ((b * maxp + 1, hkv, PAGE, D), BF16)
    return [((b, s, HQ, D), BF16), pool, pool,
            ((b, maxp), I32), ((b,), I32)] + [((b,), I32)] * (s > 1)


# --- Phi-4-mini-flash at its published widths (paired heads: 40 padded
# queries of 128 over 10 pooled KV heads, scale 1/8; d_inner 5120,
# d_state 16; window 512; page 16; the cell's batch 64, max_seq 4096)
P4_B, P4_HQ, P4_HP, P4_E, P4_N, P4_W, P4_SEQ = 64, 40, 10, 5120, 16, 512, 4096
F32 = jnp.float32


def _ssm_scan(x, dt, bm, cm, a, d, s0, n):
    from triton_dist_tpu.kernels.ssm import selective_scan
    return selective_scan(x, dt, bm, cm, a, d, s0, n)


def _ssm_step(x, dt, bm, cm, a, d, s, keep):
    from triton_dist_tpu.kernels.ssm import ssm_step
    return ssm_step(x, dt, bm, cm, a, d, s, keep)


def _window_prefill(q, k, v):
    from triton_dist_tpu.kernels.flash_attn import flash_decode
    return flash_decode(q, k, v, jnp.int32(k.shape[2]), scale=0.125,
                        window=P4_W)


def _ring_decode(q, k, v, kv_lens):
    from triton_dist_tpu.kernels.flash_attn import flash_decode
    return flash_decode(q, k, v, jnp.max(kv_lens), scale=0.125,
                        kv_lens=kv_lens)


def _paired_paged_decode(q, pk, pv, table, kv_lens):
    from triton_dist_tpu.kernels.paged_kv import flash_decode_paged
    return flash_decode_paged(q, pk, pv, table, jnp.max(kv_lens),
                              scale=0.125, kv_lens=kv_lens)


_P4_POOL = ((P4_B * (P4_SEQ // PAGE) + 1, P4_HP, PAGE, D), BF16)


# --- DeepSeek-V3 at its published widths, the shapes of the cell
# deepseek-v3-ep16.longgen-saturated: 128 slots, max_seq 4096, page 16;
# a latent row of 512 + 64 values padded to 640 lanes; 128 query heads
# over the one latent head; 16 held experts of 7168 x 2 x 2048 / 2048 x
# 7168; a 1,024-token admission
DS_B, DS_H, DS_W, DS_RANK, DS_SEQ = 128, 128, 640, 512, 4096
DS_D, DS_F, DS_E = 7168, 2048, 16
_DS_POOL = ((DS_B * (DS_SEQ // PAGE) + 1, 1, PAGE, DS_W), BF16)


def _latent_paged_decode(q, pool, table, kv_lens):
    from triton_dist_tpu.kernels.paged_kv import flash_decode_paged
    return flash_decode_paged(q, pool, None, table, jnp.max(kv_lens),
                              scale=0.135, kv_lens=kv_lens, v_cols=DS_RANK)


def _expanded_prefill(q, k, v):
    from triton_dist_tpu.kernels.flash_attn import flash_decode
    return flash_decode(q, k, v, jnp.int32(k.shape[2]), scale=0.135)


def _ragged_expert_rows(pairs):
    """The local stage of an expert layer (layers/ep_moe.py
    `expert_rows`) at `pairs` routed (token, expert) pairs: both ragged
    grouped GEMMs, the first with its SwiGLU."""
    def fn(x, eid, wgu, wd):
        from triton_dist_tpu.layers.ep_moe import expert_rows
        return expert_rows(x, jnp.arange(pairs) // 8, eid, wgu, wd)
    return fn


def _ragged_args(pairs):
    return [((pairs // 8, DS_D), BF16), ((pairs,), I32),
            ((DS_E, DS_D, 2 * DS_F), BF16), ((DS_E, DS_F, DS_D), BF16)]


# --- Keye-VL-2.0's language model at its published widths (learned
# sparse attention: 32 query heads of 128 over 4 KV heads, K and V in
# one plane of 8 head rows a page, a per-slot index plane of one 64-wide
# key, two positions a 128-lane row, 16 indexer heads, topk 2,048; the
# cell's batch 32, max_seq 20,480, page 16; a 16,384-token admission)
KY_B, KY_HQ, KY_HKV, KY_HI, KY_DI, KY_SEQ, KY_P = 32, 32, 4, 16, 64, 20480, 16384
KY_MAXP = KY_SEQ // PAGE
_KY_KV = ((KY_B * KY_MAXP + 1, 2 * KY_HKV, PAGE, D), BF16)


def _sa_index(qi, w, keys, kv_lens):
    from triton_dist_tpu.kernels.sparse_attn import index_scores
    out = index_scores(qi, w, keys, kv_lens, scale=0.03125)
    assert out.shape == qi.shape[:2] + (2 * keys.shape[1],), out.shape
    return out


def _sa_select(scores, kv_lens):
    from triton_dist_tpu.kernels.sparse_attn import select_topk
    return select_topk(
        scores, jnp.arange(scores.shape[1])[None] < kv_lens[:, None], 2048)


def _sa_decode_walk(q, pool, table, kv_lens, sel):
    from triton_dist_tpu.kernels.paged_kv import flash_decode_paged
    return flash_decode_paged(q, pool, None, table, jnp.max(kv_lens),
                              kv_lens=kv_lens, fused=True, sel=sel)


def _sa_prefill_attend(q, k, v, n, sel):
    from triton_dist_tpu.kernels.sparse_attn import selected_attention
    return selected_attention(q, k, v, sel, n, scale=D ** -0.5)


# --- Trinity-Mini (afmoe) at its published widths, the shapes of the
# cell trinity-mini-ep8.agent-saturated: 64 slots, max_seq 12,288, page
# 16; 32 query heads of 128 over 4 KV heads; a ring of 2,048 rows a slot
# and window layer; K and V in one plane of 8 head rows a page in the
# full layers; 16 held experts of 2048 x 2 x 1024 / 1024 x 2048; an
# 8,192-token admission, 256 query rows a scanned block
AF_B, AF_HQ, AF_HKV, AF_W, AF_SEQ, AF_P = 64, 32, 4, 2048, 12288, 8192
AF_D, AF_F, AF_E = 2048, 1024, 16
_AF_RING = ((AF_B, AF_HKV, AF_W, D), BF16)
_AF_KV = ((AF_B * (AF_SEQ // PAGE) + 1, 2 * AF_HKV, PAGE, D), BF16)


def _afmoe_fused_walk(q, pool, table, kv_lens):
    from triton_dist_tpu.kernels.paged_kv import flash_decode_paged
    return flash_decode_paged(q, pool, None, table, jnp.max(kv_lens),
                              kv_lens=kv_lens, fused=True)


def _afmoe_prefill(window):
    def fn(q, k, v):
        from triton_dist_tpu.layers.gated_attn import prefill_attention
        return prefill_attention(q, k, v, window=window, scale=D ** -0.5,
                                 impl="flash", scope="x")
    return fn


def _afmoe_ragged(pairs):
    def fn(x, eid, wgu, wd):
        from triton_dist_tpu.layers.ep_moe import expert_rows
        return expert_rows(x, jnp.arange(pairs) // 8, eid, wgu, wd)
    return fn, [((pairs // 8, AF_D), BF16), ((pairs,), I32),
                ((AF_E, AF_D, 2 * AF_F), BF16), ((AF_E, AF_F, AF_D), BF16)]


_AF_PROMPT = [((AF_P, AF_HQ, D), BF16), ((AF_P, AF_HKV, D), BF16),
              ((AF_P, AF_HKV, D), BF16)]

# --- Falcon-H1-34B at its published widths, the shapes of the cell
# falcon-h1-34b-pp18.longreply-saturated: 64 slots; 32 SSM heads of 128
# channels in 2 groups, a state of 256; a 1,024-token admission in
# chunks of 128
FH_B, FH_H, FH_P, FH_G, FH_N, FH_S = 64, 32, 128, 2, 256, 1024


def _ssd_scan(x, dt, bm, cm, a, d, s0, n):
    from triton_dist_tpu.kernels.ssd import ssd_scan
    return ssd_scan(x, dt, bm, cm, a, d, s0, n)


def _ssd_step(x, dt, bm, cm, a, d, s, keep):
    from triton_dist_tpu.kernels.ssd import ssd_step
    return ssd_step(x, dt, bm, cm, a, d, s, keep)

# name -> (function, [(shape, dtype), ...]); () is a traced scalar
CASES = {
    # the window layers' walk of decode: every slot's ring of 2,048
    # rotated rows, rep 8
    "afmoe_ring_decode_b64": (
        _ring_decode, [((AF_B, 1, AF_HQ, D), BF16), _AF_RING, _AF_RING,
                       ((AF_B,), I32)]),
    # the full layers' walk: K and V in one page of 8 head rows (32 KiB a
    # copy), 768 table columns, no selection
    "afmoe_fused_paged_decode_b64": (
        _afmoe_fused_walk, [((AF_B, 1, AF_HQ, D), BF16), _AF_KV,
                            ((AF_B, AF_SEQ // PAGE), I32), ((AF_B,), I32)]),
    # an 8,192-token admission's attention, the whole scanned program:
    # a window layer's blocks over the 2,304 keys they can see, a full
    # layer's over the prompt up to their last row
    "afmoe_window_prefill_t8192": (_afmoe_prefill(AF_W), _AF_PROMPT),
    "afmoe_full_prefill_t8192": (_afmoe_prefill(0), _AF_PROMPT),
    # the ragged grouped GEMMs over 16 held experts: a decode tick's 512
    # pairs (~64 of them held) and an admission's 65,536 (~8,192)
    "afmoe_ragged_gmm_decode_pairs512": _afmoe_ragged(512),
    "afmoe_ragged_gmm_admit_pairs65536": _afmoe_ragged(65536),
    # the decode step's index scores: 32 slots' one row each over the
    # per-slot plane, ten blocks of 2,048 positions a slot: [32, 20480]
    "keye_index_walk_b32": (
        _sa_index, [((KY_B, 1, KY_HI, KY_DI), BF16), ((KY_B, 1, KY_HI), F32),
                    ((KY_B, KY_SEQ // 2, 128), BF16), ((KY_B,), I32)]),
    # its selection: 2,048 of up to 20,480 scores a slot (XLA, no kernel:
    # compiled with the walk it feeds)
    # the paged walk under the selection: K and V in one page of 8 head
    # rows (32 KiB a copy), rep 8, a [32, 20480] mask
    "keye_selected_walk_b32": (
        lambda q, pool, table, lens, sc: _sa_decode_walk(
            q, pool, table, lens, _sa_select(sc, lens)),
        [((KY_B, 1, KY_HQ, D), BF16), _KY_KV, ((KY_B, KY_MAXP), I32),
         ((KY_B,), I32), ((KY_B, KY_SEQ), F32)]),
    # the admission: 256 query rows' index scores against the prompt's
    # 16,384 packed index keys (the same kernel, one "slot"), and their
    # attention under the selection
    "keye_index_prefill_q256_t16384": (
        _sa_index, [((1, 256, KY_HI, KY_DI), BF16), ((1, 256, KY_HI), F32),
                    ((1, KY_P // 2, 128), BF16), ((1,), I32)]),
    "keye_selected_prefill_q256_t16384": (
        _sa_prefill_attend, [((256, KY_HQ, D), BF16),
                             ((KY_HKV, KY_P, D), BF16),
                             ((KY_HKV, KY_P, D), BF16), ((), I32),
                             ((256, KY_P), jnp.bool_)]),
    # the absorbed decode walk: 128 slots, one latent head, rep 128,
    # keys 640 lanes wide, values their first 512 columns (20 KiB a copy)
    "dsv3_latent_walk_b128": (
        _latent_paged_decode, [((DS_B, 1, DS_H, DS_W), BF16), _DS_POOL,
                               ((DS_B, DS_SEQ // PAGE), I32),
                               ((DS_B,), I32)]),
    # the expanded prefill attend: 128 heads, q/k 192 and v 128 padded
    # to 256, the last 256 query rows of a 1,024-token prompt
    "dsv3_expanded_prefill_q256_t1024": (
        _expanded_prefill, [((1, 256, DS_H, 256), BF16),
                            ((1, DS_H, 1024, 256), BF16),
                            ((1, DS_H, 1024, 256), BF16)]),
    # the ragged grouped GEMMs over 16 held experts: a decode tick's
    # worst case (128 tokens x 8 pairs, ~64 of them held) and a
    # 1,024-token admission's (8,192 pairs, ~512 held)
    "dsv3_ragged_gmm_decode_pairs1024": (
        _ragged_expert_rows(1024), _ragged_args(1024)),
    "dsv3_ragged_gmm_admit_pairs8192": (
        _ragged_expert_rows(8192), _ragged_args(8192)),
    # the admission's chunked scan over a 2,048-token prompt, and the
    # decode's one-token update of 64 slots' states
    "phi4_ssm_scan_s2048": (
        _ssm_scan, [((2048, P4_E), F32), ((2048, P4_E), F32),
                    ((2048, P4_N), F32), ((2048, P4_N), F32),
                    ((P4_N, P4_E), F32), ((P4_E,), F32),
                    ((P4_N, P4_E), F32), ((), I32)]),
    "phi4_ssm_step_b64": (
        _ssm_step, [((P4_B, P4_E), F32), ((P4_B, P4_E), F32),
                    ((P4_B, P4_N), F32), ((P4_B, P4_N), F32),
                    ((P4_N, P4_E), F32), ((P4_E,), F32),
                    ((P4_B, P4_N, P4_E), F32), ((P4_B,), jnp.bool_)]),
    # Falcon-H1's SSD kernels: the admission's chunked scan over a
    # 1,024-token prompt, and the decode's one-token update of 64 slots'
    # [32, 256, 128] float32 states, aliased in place
    "fh_ssd_scan_s1024": (
        _ssd_scan, [((FH_S, FH_H, FH_P), F32), ((FH_S, FH_H), F32),
                    ((FH_S, FH_G, FH_N), F32), ((FH_S, FH_G, FH_N), F32),
                    ((FH_H,), F32), ((FH_H,), F32),
                    ((FH_H, FH_N, FH_P), F32), ((), I32)]),
    "fh_ssd_step_b64": (
        _ssd_step, [((FH_B, FH_H, FH_P), F32), ((FH_B, FH_H), F32),
                    ((FH_B, FH_G, FH_N), F32), ((FH_B, FH_G, FH_N), F32),
                    ((FH_H,), F32), ((FH_H,), F32),
                    ((FH_B, FH_H, FH_N, FH_P), F32),
                    ((FH_B,), jnp.bool_)]),
    # window attention of a prefill: 256 query rows over the 768 keys
    # they can see, the window mask in the kernel
    "phi4_window_prefill_q256": (
        _window_prefill, [((1, 256, P4_HQ, D), BF16),
                          ((1, P4_HP, 768, D), BF16),
                          ((1, P4_HP, 768, D), BF16)]),
    # the window walk of decode: every slot's ring of 512 rows
    "phi4_ring_decode_b64": (
        _ring_decode, [((P4_B, 1, P4_HQ, D), BF16),
                       ((P4_B, P4_HP, P4_W, D), BF16),
                       ((P4_B, P4_HP, P4_W, D), BF16), ((P4_B,), I32)]),
    # the full and cross layers' walk of layer 17's pool: 64 slots of
    # 10 paired heads x 4 padded query rows (40 KiB a copy), 256 table
    # columns
    "phi4_paged_decode_b64": (
        _paired_paged_decode, [((P4_B, 1, P4_HQ, D), BF16), _P4_POOL,
                               _P4_POOL,
                               ((P4_B, P4_SEQ // PAGE), I32),
                               ((P4_B,), I32)]),
    # Engine.prefill: B=8 prompts of 128 into the contiguous cache
    "flash_prefill_b8_s128": (
        _flash_decode, [_q(B, 128), _kv(B, T), _kv(B, T), ((), I32)]),
    # paged admission: one 80-token prompt through the 1-row scratch
    # (slot capacity + one 8-row bucket), and the 16-token suffix left
    # after a 64-token prefix hit
    "flash_admit_s80": (
        _flash_decode, [_q(1, 80), _kv(1, T + 8), _kv(1, T + 8),
                        ((), I32)]),
    "flash_admit_s16": (
        _flash_decode, [_q(1, 16), _kv(1, T + 8), _kv(1, T + 8),
                        ((), I32)]),
    # Engine.decode: one token per row over the contiguous cache
    "flash_decode_b8": (
        _flash_decode, [_q(B, 1), _kv(B, T), _kv(B, T), ((), I32)]),
    "flash_decode_slots_b8": (
        _flash_decode_slots, [_q(B, 1), _kv(B, T), _kv(B, T),
                              ((B,), I32)]),
    # the serving tick: per-slot lengths through the page table
    "flash_decode_paged_b8_page16": (
        _flash_decode_paged, [_q(B, 1), _POOL, _POOL,
                              ((B, MAXP), I32), ((B,), I32)]),
    # the same walk at the benchmark's cells (B=32 slots, max_seq
    # 2048 = 128 table columns of page 16): one chip's 32 slots x 8
    # heads x 2 query rows (32 KiB a copy, W = 1), and a TP=4 chip's
    # 32 x 2 x 8 (8 KiB, W = 4)
    "flash_decode_paged_cell_1chip": (
        _flash_decode_paged, _paged_cell(hkv=8)),
    "flash_decode_paged_cell_tp4": (
        _flash_decode_paged, _paged_cell(hkv=2)),
    # the mixed tick's 16-token window (chunked prefill, spec verify):
    # 32 query rows a stream
    "flash_decode_paged_cell_windows": (
        _flash_decode_paged_windows, _paged_cell(hkv=8, s=16)),
    # cache row insert: a whole prompt, and one 8-row decode tile
    "kv_update_prefill_s128": (
        _kv_update, [_kv(B, T), _kv(B, 128), ((), I32)]),
    "kv_update_tile_s8": (
        _kv_update, [_kv(B, T), _kv(B, 8), ((), I32)]),
    # fused SwiGLU on the packed [gate | up] projection
    "swiglu_prefill_m1024": (_swiglu, [((B * 128, 2 * FFN), BF16)]),
    "swiglu_decode_m8": (_swiglu, [((B, 2 * FFN), BF16)]),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip, for_chip):
    fn, args = CASES[name]
    shapes = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
              for s, dt in args]
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text(), (
        f"{name}: no Mosaic kernel in the compiled program")


@pytest.mark.parametrize("hkv", [8, 2], ids=["1chip", "tp4"])
def test_kv_append_updates_the_pool_in_place(hkv, one_chip, for_chip):
    """The decode scan's append (append_page_rows) then walk, over
    DONATED planes, at a cell's pool (_paged_cell): the compiled
    program holds no copy of a plane, as [NP, hkv, page, d] or as the
    scatter's [NP*hkv, page, d] view — what the alias must give and
    interpret mode cannot show. The append is its own `kv_append`
    custom call, which neither the walk's roofline pattern nor the
    fused GEMM + all-reduce's picks up (benchmark/metrics)."""
    import json
    import re
    from triton_dist_tpu.kernels.paged_kv import (append_page_rows,
                                                  flash_decode_paged)
    q, pool, _, table, lens = _paged_cell(hkv)
    b, maxp = table[0]
    (np_, _, page, d), _ = pool

    def scan(pk, pv, q, k, v, tbl, pos):
        def step(c, x):
            pk, pv = append_page_rows(*c, tbl[jnp.arange(b), x // page],
                                      x % page, k, v)
            return (pk, pv), flash_decode_paged(q, pk, pv, tbl,
                                                jnp.max(x + 1),
                                                kv_lens=x + 1)
        return jax.lax.scan(step, (pk, pv), pos + jnp.arange(4)[:, None])

    shapes = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in
              (pool, pool, q, ((b, hkv, d), BF16), ((b, hkv, d), BF16),
               table, lens)]
    text = jax.jit(scan, donate_argnums=(0, 1)).lower(
        *shapes).compile().as_text()
    planes = (f"[{np_},{hkv},{page},{d}]", f"[{np_ * hkv},{page},{d}]")
    ops = [line.strip() for line in text.splitlines()]
    copies = [o for o in ops if re.search(r" copy(-start)?\(", o)
              and any(p in o.split(" copy")[0] for p in planes)]
    assert not copies, copies[:2]
    appends = [o for o in ops if o.startswith("%kv_append")]
    assert len(appends) == 1 and "custom-call(" in appends[0]
    # both planes pinned to HBM (memory color 0): a TP=4 plane fits
    # VMEM, and left to XLA the whole scan moved planes there
    assert '"output_memory_colors":["0","0"]' in appends[0]

    def pattern(metric):
        with open(os.path.join(os.path.dirname(__file__), "..",
                               "benchmark", "metrics",
                               f"{metric}.json")) as f:
            return re.compile(json.load(f)["args"]["patterns"][0])
    for metric in ("decode_attn_roofline.sat", "tp_gemm_ar_roofline.sat"):
        assert not pattern(metric).search(appends[0]), metric
    assert any(pattern("decode_attn_roofline.sat").search(o) for o in ops)


def test_int8_pool_compiles_at_page_128_only(one_chip, for_chip):
    """The int8 pool's scale planes are [NP, Hkv, page] f32 and the
    walk copies one page's rows of them: at page 16, as served, that is
    a 16-lane slice Mosaic refuses (so did the per-head planes and the
    BlockSpec walk before PR 30), and the variant runs in the
    interpreter only. Recorded here so that the day a wider page or
    another plane lifts it, this test says so (PERF.md, open
    questions)."""
    from triton_dist_tpu.kernels.paged_kv import flash_decode_paged

    def lower(page, maxp=16):
        np_ = B * maxp + 1
        pool = jax.ShapeDtypeStruct((np_, HKV, page, D), jnp.int8,
                                    sharding=one_chip)
        scale = jax.ShapeDtypeStruct((np_, HKV, page), jnp.float32,
                                     sharding=one_chip)
        shapes = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
                  for s, dt in (_q(B, 1), ((B, maxp), I32),
                                ((B,), I32))]
        return jax.jit(lambda q, t, l, pk, pv, sk, sv: flash_decode_paged(
            q, pk, pv, t, jnp.max(l), kv_lens=l, k_scale=sk, v_scale=sv)
        ).lower(*shapes, pool, pool, scale, scale)

    assert "tpu_custom_call" in lower(128).compile().as_text()
    with pytest.raises(Exception, match="(?i)mosaic|tiling|align|shape"):
        lower(16).compile()


# --- the TP=4 comm kernels (chip_smoke.py --chips 4 runs gemm_ar; the
# "dist" backend runs the other two), MLP projections at 1.7B widths.
# name -> (kernel, M, A [M, K] spec, B [K, N] spec, K, N)
COMM_CASES = {
    # down projection, decode rows and prefill rows: C replicated
    "gemm_allreduce_m8": ("gemm_allreduce", B, P(None, "tp"),
                          P("tp", None), FFN, HIDDEN),
    "gemm_allreduce_m1024": ("gemm_allreduce", B * 128, P(None, "tp"),
                             P("tp", None), FFN, HIDDEN),
    # gate|up projection from row-sharded activations
    "ag_gemm_m1024": ("ag_gemm", B * 128, P("tp", None), P(None, "tp"),
                      HIDDEN, 2 * FFN),
    # down projection back to row-sharded activations
    "gemm_rs_m1024": ("gemm_rs", B * 128, P(None, "tp"), P("tp", None),
                      FFN, HIDDEN),
}


@pytest.mark.parametrize("name", sorted(COMM_CASES))
def test_comm_kernel_compiles_for_four_v5e(name, four_chips, for_chip):
    from triton_dist_tpu import kernels
    kernel, m, a_spec, b_spec, k, n = COMM_CASES[name]
    fn = getattr(kernels, kernel)
    a = jax.ShapeDtypeStruct((m, k), BF16,
                             sharding=NamedSharding(four_chips, a_spec))
    b = jax.ShapeDtypeStruct((k, n), BF16,
                             sharding=NamedSharding(four_chips, b_spec))
    compiled = jax.jit(
        lambda a, b: fn(a, b, mesh=four_chips)).lower(a, b).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, f"{name}: no Mosaic kernel"
    # the collective lives INSIDE the kernel: XLA adds none of its own
    assert "all-reduce(" not in text and "all-gather(" not in text \
        and "reduce-scatter(" not in text, f"{name}: XLA collective"
