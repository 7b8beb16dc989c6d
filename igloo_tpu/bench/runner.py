"""Staging helpers + a single-query debug worker.

The shared staging helpers (`ensure_staged`, `stage_dir`, `make_engine`;
chip_smoke.py builds its session engine with the last) and a per-query CLI
useful for isolating one query's behavior in a fresh process:

    python -m igloo_tpu.bench.runner q7 1 /tmp/igloo_bench_sf1 5

A pathological XLA compile in-process is routed to the staged executor by the
hint store's armed `nofuse` sentinel (exec/fused.py arms it before each
first-ever fused compile and clears it after success; a process killed
mid-compile leaves it armed, so the NEXT process avoids the fused program).
"""
from __future__ import annotations

import json
import os
import sys
import time


def stage_dir(sf: float) -> str:
    return os.environ.get(
        "BENCH_STAGE_DIR",
        os.path.join("/tmp", f"igloo_bench_sf{sf:g}"))


def ensure_staged(sf: float) -> str:
    """Generate + write the TPC-H tables once; reuse across processes."""
    import pyarrow.parquet as pq

    from igloo_tpu.bench.tpch import gen_tables
    d = stage_dir(sf)
    marker = os.path.join(d, ".complete")
    if os.path.exists(marker):
        return d
    os.makedirs(d, exist_ok=True)
    t0 = time.perf_counter()
    tables = gen_tables(sf=sf)
    for name, tbl in tables.items():
        pq.write_table(tbl, os.path.join(d, f"{name}.parquet"))
    with open(marker, "w") as f:
        f.write(str(time.time()))
    print(f"staged sf={sf} in {time.perf_counter() - t0:.1f}s -> {d}",
          file=sys.stderr, flush=True)
    return d


def make_engine(d: str):
    from igloo_tpu.connectors.parquet import ParquetTable
    from igloo_tpu.engine import QueryEngine
    engine = QueryEngine()
    for name in ("region", "nation", "supplier", "part", "partsupp",
                 "customer", "orders", "lineitem"):
        engine.register_table(name, ParquetTable(
            os.path.join(d, f"{name}.parquet")))
    return engine


def main(argv=None) -> int:
    argv = argv if argv is not None else sys.argv[1:]
    q, sf_s, d, trials_s = argv
    sf, trials = float(sf_s), int(trials_s)
    from igloo_tpu.bench.tpch import QUERIES
    engine = make_engine(d)
    sql = QUERIES[q]

    t0 = time.perf_counter()
    engine.execute(sql)
    cold = time.perf_counter() - t0
    # adopt cardinality hints (recompiles) until run time stops collapsing
    prev = cold
    for _ in range(4):
        engine.result_cache.clear()
        t0 = time.perf_counter()
        engine.execute(sql)
        cur = time.perf_counter() - t0
        if cur > 0.5 * prev:
            break
        prev = cur
    warm = []
    for _ in range(trials):
        engine.result_cache.clear()
        t0 = time.perf_counter()
        engine.execute(sql)
        warm.append(time.perf_counter() - t0)
    t0 = time.perf_counter()
    engine.execute(sql)
    cached = time.perf_counter() - t0
    print(json.dumps({"q": q, "cold_s": round(cold, 4),
                      "warm_trials": [round(w, 4) for w in warm],
                      "cached_s": round(cached, 4)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
