#!/usr/bin/env bash
# Single CI entry point:
#   1. docs reference check (no dangling *.md citations in src/),
#   2. tier-1 test suite (default selection: -m 'not slow'),
#   3. per-test wall-clock budget: any non-slow test whose call phase
#      exceeds 60 s fails the run (shrink it or mark it slow).
#
#   bash scripts/ci.sh [extra pytest args...]
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

echo "== lint (ruff) =="
# pyproject.toml carries the [tool.ruff] config; the container image may
# not ship a ruff binary (no network installs), so gate on its presence
if command -v ruff >/dev/null 2>&1; then
    ruff check src tests scripts examples
else
    echo "ruff not installed; skipping lint"
fi

echo "== docs reference check =="
python scripts/check_docs.py

echo "== kernel launch-contract check =="
# statically verify every BlockSpec index map / output coverage / alias /
# scalar-prefetch domain over the full tuning candidate spaces
timeout 60 python -m repro.analysis.check

echo "== distributed ownership + paged-pool model check =="
# SP cross-shard ownership/halo/comm over mesh sizes 1..8 (zero
# devices) and a bounded exhaustive model check of the real PagePool
timeout 60 python -m repro.analysis.check --dist --pool

echo "== tier-1 tests (durations-budgeted) =="
report="$(mktemp)"
trap 'rm -f "$report"' EXIT
# --durations=0 reports every phase >= 5ms; the budget checker reads
# the 'call' rows.  pipefail propagates a pytest failure through tee.
python -m pytest -q --durations=0 "$@" | tee "$report"

echo "== per-test budget =="
python scripts/check_test_budget.py "$report" --budget 60

echo "== kernel launch-policy autotune smoke =="
# measured autotune round-trip on a tiny shape, against a throwaway
# cache dir so CI never touches (or depends on) the checkout's
# .repro_tune tables;
# the second invocation proves the table survives a process boundary
# and is applied without re-measurement
tune_cache="$(mktemp -d)"
REPRO_TUNE_CACHE="$tune_cache" timeout 60 \
    python -m repro.kernels.tuning --autotune-smoke
REPRO_TUNE_CACHE="$tune_cache" timeout 60 \
    python -m repro.kernels.tuning --assert-cached

echo "== examples smoke (serve_batched, dense + paged + int8) =="
# tiny-config end-to-end smokes, held to the same 60 s budget each
timeout 60 python examples/serve_batched.py \
    --requests 4 --slots 2 --new-tokens 4 > /dev/null
timeout 60 python examples/serve_batched.py --paged --pool-pages 24 \
    --requests 4 --slots 2 --new-tokens 4 > /dev/null
timeout 60 python examples/serve_batched.py --paged --cache-dtype int8 \
    --pool-pages 24 --requests 4 --slots 2 --new-tokens 4 > /dev/null
echo "examples OK"

echo "== telemetry smoke (profiler trace + prometheus vs pinned schemas) =="
# telemetry-enabled paged serve recorded into a jax.profiler trace
# directory; pallas_interpret keeps the launch path (and therefore the
# kernel.* analytic-traffic counters) live on CPU.  The artifacts are
# validated by the SAME repro.obs.export validators the unit tests pin,
# so CI and tests cannot drift apart.
obs_dir="$(mktemp -d)"
timeout 60 python examples/serve_batched.py --paged --pool-pages 24 \
    --decode-impl pallas_interpret --requests 4 --slots 2 \
    --new-tokens 4 --telemetry --trace-out "$obs_dir/trace" \
    --prom-out "$obs_dir/metrics.prom" > /dev/null
timeout 60 python scripts/check_telemetry.py \
    --trace-dir "$obs_dir/trace" --prom "$obs_dir/metrics.prom" \
    --require-kernel-traffic
