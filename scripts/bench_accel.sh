#!/usr/bin/env bash
# One-command accelerator benchmark run.
#
#   bash scripts/bench_accel.sh                     # all sweeps
#   bash scripts/bench_accel.sh --sweeps dist,multihost --quick
#
# Runs the kernel microbench on the accelerator and warms the wisdom store
# next to the output, so a single invocation on real hardware both
# refreshes benchmarks/BENCH_kernels.json with accelerator-tagged records
# and leaves a store later planning sessions are served from.  JAX is
# touched by one process only (the microbench), which refuses to run when
# it finds no accelerator: this script never falls back to the CPU.
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

OUT="${BENCH_OUT:-benchmarks/BENCH_kernels.json}"
WISDOM="${BENCH_WISDOM:-benchmarks/wisdom.json}"

echo "benching -> ${OUT} (wisdom: ${WISDOM})"
exec python -m benchmarks.kernel_microbench --require-accelerator \
    --out "${OUT}" --wisdom "${WISDOM}" "$@"
