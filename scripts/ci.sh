#!/usr/bin/env bash
# One-command gate for every PR: tier-1 tests, docs link check, and fast
# benchmark smokes (CPU, Pallas kernels in interpret mode).
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

echo "=== tier-1: pytest ==="
python -m pytest -x -q

echo "=== docs: relative-link check ==="
python scripts/check_docs_links.py

echo "=== smoke: Fig. 7/8 energy benchmark ==="
python -m benchmarks.run --only fig78

echo "=== smoke: online measurement-feedback gate ==="
python -m benchmarks.bench_online --smoke

echo "=== smoke: heterogeneous-pool gate ==="
python -m benchmarks.bench_hetero --smoke

echo "=== smoke: power-cap gate ==="
python -m benchmarks.bench_powercap --smoke

echo "=== smoke: preemptive-rescue gate ==="
python -m benchmarks.bench_preempt --smoke

echo "=== smoke: multi-tenant SLA-tier gate ==="
python -m benchmarks.bench_tenants --smoke

echo "=== smoke: cold-start synthesis gate ==="
python -m benchmarks.bench_coldstart --smoke

echo "=== smoke: multi-rack federation gate ==="
python -m benchmarks.bench_federation --smoke

echo "=== smoke: model-derived workload gate ==="
python -m benchmarks.bench_models_sched --smoke

echo "=== smoke: vectorized decision core + perf regression gate ==="
DECIDE_JSON="$(mktemp /tmp/bench_decide_smoke.XXXXXX.json)"
python -m benchmarks.bench_decide --smoke --json "$DECIDE_JSON"
python scripts/check_perf.py --current "$DECIDE_JSON"
rm -f "$DECIDE_JSON"

echo "=== differential harness: preemptive-engine identity + conservation ==="
python -m pytest -q tests/test_differential.py

echo "=== golden traces: behavior-drift gate ==="
python -m pytest -q tests/test_golden.py

echo "=== ci.sh: all green ==="
