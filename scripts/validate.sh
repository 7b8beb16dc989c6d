#!/usr/bin/env bash
# Full validation pipeline — mirror of the reference's scripts/validate.sh
# (fmt + clippy -D warnings + check + build + test): lint strict, then the
# whole suite on the virtual 8-device CPU mesh.
set -euo pipefail
cd "$(dirname "$0")/.."

echo "== igloo-lint (hazards + contracts + thread-roles / lock-order) =="
# hard wall-time pin: the whole-program rules must not erode the "fast
# enough to run on every commit" property (docs/static_analysis.md)
timeout 10 python -m igloo_tpu.lint
python -m igloo_tpu.lint --stale-allows -q

echo "== ruff (lint) =="
if python -c "import ruff" 2>/dev/null || command -v ruff >/dev/null; then
  python -m ruff check igloo_tpu tests __graft_entry__.py
else
  echo "ruff not installed here; skipping lint (CI runs it)"
fi

echo "== 2-worker shuffle-join smoke (fragment-tier exchange) =="
python scripts/shuffle_smoke.py

echo "== encoded smoke (compressed execution A/B: identical rows, fewer bytes) =="
python scripts/encoded_smoke.py

echo "== trace smoke (flight recorder: stitched 2-worker Perfetto trace) =="
python scripts/trace_smoke.py

echo "== watchtower smoke (sampler + slow-query escalation + event journal) =="
python scripts/watchtower_smoke.py

echo "== two-level smoke (2 workers x 2 devices: mesh tier inside the exchange) =="
python scripts/twolevel_smoke.py

echo "== chaos smoke (injected faults + worker kill + hung worker) =="
python scripts/chaos_smoke.py

echo "== out-of-core smoke (2-worker GRACE buckets: spill-and-stream under budget) =="
python scripts/oocore_smoke.py

echo "== storage smoke (fault-injected object store: retries + snapshot re-plan + bounded prefetch) =="
python scripts/storage_smoke.py

echo "== persistent compile-cache smoke (two-process cold/warm) =="
python scripts/compile_cache_smoke.py

echo "== adaptive smoke (skew sketch -> salted exchange beats unsalted) =="
python scripts/adaptive_smoke.py

echo "== serving smoke (64-client burst vs bounded admission queue) =="
python scripts/serving_smoke.py

echo "== pytest (fast tier, virtual 8-device CPU mesh) =="
python -m pytest tests/ -q -m "not slow"

echo "== pytest (slow tier: the tests marked slow one by one) =="
if [ "${SKIP_SLOW:-0}" = "1" ]; then
  echo "SKIP_SLOW=1: skipping (CI and the round driver still run everything)"
else
  python -m pytest tests/ -q -m slow
fi

echo "== pytest (full tier: all 22 TPC-H queries sharded) =="
if [ "${IGLOO_FULL_TPCH:-0}" = "1" ]; then
  python -m pytest tests/test_parallel.py -q -k test_sharded_tpch_full
else
  echo "IGLOO_FULL_TPCH != 1: skipping the ~10-min full sharded sweep"
fi

echo "== graft entry (single-chip jit + 8-device dryrun) =="
python __graft_entry__.py

echo "validate: OK"
