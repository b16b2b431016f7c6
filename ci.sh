#!/usr/bin/env bash
# Tier-1 verify gate (see ROADMAP.md) — one command for CI and local use.
#
# The full pytest suite on the CPU: the engine cross-validation, the pinned
# simulated counts and tuner fronts, the static lint of examples/, and the
# harness tests of bench/.  It writes no tracked file.  The benchmark itself
# is bench/run.py, declared in BENCHMARK.json, and runs on the chip.
set -euo pipefail
cd "$(dirname "$0")"
export JAX_PLATFORMS=cpu
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m pytest -x -q "$@"
