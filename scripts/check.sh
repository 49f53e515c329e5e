#!/usr/bin/env bash
# Smoke targets.
#   scripts/check.sh [extra pytest args...]   full tier-1 + fast benchmarks
#                                             (runs the kernels tier first)
#   scripts/check.sh fast [extra pytest args] unit tests minus the slow
#                                             trainer/distributed suites
#   scripts/check.sh kernels [extra args]     batched Pallas kernels
#                                             (interpret mode) vs refs,
#                                             backend registry, and the
#                                             pool-parity pins
#   scripts/check.sh quant [extra args]       quantized second-moment pools
#                                             (fp32 parity, int8/bf16,
#                                             cross-dtype checkpoints)
#   scripts/check.sh async [extra args]       async refresh pipeline:
#                                             step-shifted parity matrix
#                                             first (schedule x dtype x
#                                             reduction), then donation +
#                                             checkpoint droppability
#   scripts/check.sh tune [extra args]        kernel autotuner: candidate-
#                                             sweep parity vs XLA refs,
#                                             cache modes + round-trip,
#                                             fused-epilogue jaxpr pins,
#                                             then the committed fixture's
#                                             schema validation
#   scripts/check.sh budget [extra args]      rank-budget allocator: the
#                                             static-policy bitwise-parity
#                                             matrix first, then allocator
#                                             properties, rho_greedy
#                                             migration, and checkpoint
#                                             migration
#   scripts/check.sh serve [extra args]       serving stack: continuous-
#                                             batching parity vs the old
#                                             static path, slot reuse, FD
#                                             gradient monitor policy,
#                                             set_hyperparams no-retrace,
#                                             and the e2e shift-adapt
#                                             scenario
# Extra pytest args reach EVERY pytest invocation of the chosen tier,
# including the kernels tier that the full tier runs first.
# All tiers run a compileall syntax gate first so breakage surfaces before
# pytest collection.
set -euo pipefail
cd "$(dirname "$0")/.."

echo "--- syntax gate (python -m compileall src) ---"
python -m compileall -q src

kernels_tier() {
  # interpret-mode kernel sweeps + registry dispatch + the bitwise
  # pool-parity pins against tests/reference_impls.py
  python -m pytest -x -q \
    tests/test_kernels.py \
    tests/test_kernel_registry.py \
    tests/test_pool.py::test_pooled_engine_bitwise_matches_per_leaf \
    "$@"
}

quant_tier() {
  # quantized pool storage: fp32 bitwise pin, int8 round-trip property
  # tests, bf16 convergence tolerance, cross-dtype checkpoint migration
  python -m pytest -x -q tests/test_quantize.py "$@"
}

async_tier() {
  # parity FIRST: the step-shifted-equality matrix is the correctness
  # contract of refresh_mode="async" — run it before the donation and
  # checkpoint plumbing so a parity break fails the tier immediately
  python -m pytest -x -q \
    tests/test_async_refresh.py::test_async_committed_equals_inline \
    tests/test_async_refresh.py::test_async_shampoo_parity \
    tests/test_async_refresh.py::test_async_parity_under_sharded_stats \
    "$@"
  python -m pytest -x -q \
    tests/test_async_refresh.py \
    tests/test_trainer.py::test_train_step_donates_buffers \
    --deselect tests/test_async_refresh.py::test_async_committed_equals_inline \
    --deselect tests/test_async_refresh.py::test_async_shampoo_parity \
    --deselect tests/test_async_refresh.py::test_async_parity_under_sharded_stats \
    "$@"
}

if [[ "${1:-}" == "kernels" ]]; then
  shift
  kernels_tier "$@"
  exit 0
fi

if [[ "${1:-}" == "quant" ]]; then
  shift
  quant_tier "$@"
  exit 0
fi

tune_tier() {
  # every tile candidate in the autotuner's menu must match the XLA refs
  # (hypothesis sweeps over ragged shapes/dtypes), cache modes and the
  # reload round-trip must be deterministic, and the fused int8 epilogue's
  # no-f32-materialization jaxpr pins must hold; finally the committed
  # fixture is schema-validated against the candidate space
  python -m pytest -x -q tests/test_autotune.py "$@"
  python -m repro.kernels.autotune validate
}

if [[ "${1:-}" == "async" ]]; then
  shift
  async_tier "$@"
  exit 0
fi

if [[ "${1:-}" == "tune" ]]; then
  shift
  tune_tier "$@"
  exit 0
fi

budget_tier() {
  # parity FIRST: RankBudget(policy="static") must stay bitwise-identical
  # to the pre-budget engine across the schedule x mode x dtype matrix —
  # a parity break fails the tier before the allocator property tests,
  # the rho_greedy migration checks, and the fixed-rank checkpoint shim
  python -m pytest -x -q \
    "tests/test_rank_budget.py::test_static_policy_bitwise_parity" \
    "$@"
  python -m pytest -x -q tests/test_rank_budget.py \
    --deselect tests/test_rank_budget.py::test_static_policy_bitwise_parity \
    "$@"
}

if [[ "${1:-}" == "budget" ]]; then
  shift
  budget_tier "$@"
  exit 0
fi

serve_tier() {
  # parity FIRST: the session API must reproduce the old static-batch
  # greedy tokens across cache families before the telemetry/adaptation
  # tests run — a decode regression fails the tier immediately
  python -m pytest -x -q \
    "tests/test_serve.py::test_continuous_batching_matches_static_batch" \
    "$@"
  python -m pytest -x -q tests/test_serve.py \
    --deselect tests/test_serve.py::test_continuous_batching_matches_static_batch \
    "$@"
}

if [[ "${1:-}" == "serve" ]]; then
  shift
  serve_tier "$@"
  exit 0
fi

if [[ "${1:-}" == "fast" ]]; then
  shift
  # unit tier: drops the trainer/distributed suites plus the two
  # multi-minute convergence sweeps (convex OCO regret, all-archs forward)
  python -m pytest -x -q \
    --ignore=tests/test_trainer.py \
    --ignore=tests/test_distributed.py \
    --ignore=tests/test_dryrun.py \
    --ignore=tests/test_optim_convex.py \
    --ignore=tests/test_models.py \
    "$@"
  exit 0
fi

echo "--- kernels tier (batched Pallas vs refs + pool-parity pins) ---"
kernels_tier "$@"

# rest of tier-1; the kernels-tier files already ran above, skip re-running
# the interpret-mode Pallas sweeps (test_pool re-runs only its one pin)
python -m pytest -x -q \
  --ignore=tests/test_kernels.py \
  --ignore=tests/test_kernel_registry.py \
  "$@"

echo "--- fast benchmarks (fig1 memory + lemma-1 FD error) ---"
PYTHONPATH=src python - <<'PY'
import sys
sys.path.insert(0, "benchmarks")
import run
print("name,us_per_call,derived")
run.bench_fig1_memory()
run.bench_lem1_fd_error()
PY
