#!/usr/bin/env bash
# Env-tuned launcher: `scripts/launch.sh <command...>` runs the command
# with the allocator/XLA settings the benchmarks assume, so interactive
# runs, CI bench steps, and the committed perf baselines all see the
# same runtime configuration.
#
#   scripts/launch.sh python benchmarks/bench_engine.py --smoke
#   scripts/launch.sh python -m repro.scenarios run NAME --smoke
#
# Everything here is an override-able default: variables already set in
# the environment win.
set -euo pipefail

# tcmalloc beats glibc malloc on the host-side assembly paths (trace
# collection, ledger/timeline building); preload it when present.
if [ -z "${LD_PRELOAD:-}" ]; then
    for so in /usr/lib/x86_64-linux-gnu/libtcmalloc.so.4 \
              /usr/lib/x86_64-linux-gnu/libtcmalloc_minimal.so.4 \
              /usr/lib/libtcmalloc.so.4; do
        if [ -e "$so" ]; then
            export LD_PRELOAD="$so"
            break
        fi
    done
fi

# silence large-numpy-allocation reports and TF/absl dataset chatter
export TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD="${TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD:-60000000000}"
export TF_CPP_MIN_LOG_LEVEL="${TF_CPP_MIN_LOG_LEVEL:-4}"

# XLA_FLAGS passes through untouched: flag sets differ per backend
# build (e.g. --xla_step_marker_location exists on TPU but aborts CPU
# wheels at startup), so per-flag tuning belongs to the caller.

exec "$@"
