#!/usr/bin/env bash
# Regenerate every on-chip artifact in one command, on a machine with
# a chip. Safe to re-run; each step is independent and failures don't
# stop the rest.
#
#   bash tools/onchip_regen.sh
#
# Produces (repo root):
#   tune cache (TDTPU_TUNE_CACHE / ~/.triton_dist_tpu/tune_cache.json)
#   PERF_OPS_tpu.json            per-op SOL report (git+date stamped)
#   PROFILE_<kernel>.json/.trace.json   ablation profiles x4
#   BENCH_local.json             bench rows
set -u
cd "$(dirname "$0")/.."

echo "== backend probe =="
if ! timeout 120 python -c "import jax; assert jax.default_backend() == 'tpu', jax.default_backend()"; then
    echo "no TPU backend reachable - aborting (artifacts unchanged)"
    exit 1
fi

echo "== autotune sweep (populates the tune cache the reports read) =="
timeout 3600 python -m triton_dist_tpu.tools.sweep \
    || echo "sweep FAILED"

echo "== per-op SOL report =="
timeout 3000 python -m triton_dist_tpu.tools.perf_report \
    --json PERF_OPS_tpu.json || echo "perf_report FAILED"

echo "== kernel ablation profiles =="
timeout 3600 python -m triton_dist_tpu.tools.kprof_run --out . \
    || echo "kprof_run FAILED"

echo "== bench =="
timeout 3600 python bench.py | tee BENCH_local.json || echo "bench FAILED"

echo "== done; diff the artifacts and update README numbers =="
