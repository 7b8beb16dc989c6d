"""Per-operator query telemetry (utils/stats.py, system_tables.py).

Covers the ISSUE-3 acceptance surface that fits tier-1 time: the operator
stats tree carries actual rows + tier attribution for a 2-join query on the
device tier, for an aggregate on the chunked tier, and for a join tree on
the GRACE tier (per-partition rollup); system.metrics / system.query_log
round-trip through SQL; counter_delta() deltas are isolated across threads;
span roots are bounded; Prometheus text renders the registry."""
import json
import os
import threading

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from igloo_tpu.catalog import MemTable
from igloo_tpu.engine import QueryEngine
from igloo_tpu.utils import stats, tracing


@pytest.fixture()
def engine():
    e = QueryEngine()
    n = 400
    e.register_table("fact", pa.table({
        "fk": pa.array([i % 40 for i in range(n)], type=pa.int64()),
        "v": pa.array([float(i % 7) for i in range(n)]),
    }))
    e.register_table("dim", pa.table({
        "k": pa.array(list(range(40)), type=pa.int64()),
        "gk": pa.array([i % 4 for i in range(40)], type=pa.int64()),
    }))
    e.register_table("grp", pa.table({
        "g": pa.array(list(range(4)), type=pa.int64()),
        "name": ["a", "b", "c", "d"],
    }))
    return e


TWO_JOIN_SQL = """
    SELECT name, sum(v) AS s
    FROM fact JOIN dim ON fk = k JOIN grp ON gk = g
    GROUP BY name ORDER BY name
"""


def test_device_tier_two_join_rows(engine, monkeypatch):
    """EXPLAIN ANALYZE on a 2-join query: device tier, actual per-operator
    rows, compile/execute split, capacities in the tree. Adaptive join
    reordering is pinned OFF: the per-operator row expectations encode the
    written join order, and this test is about telemetry, not plan choice
    (tests/test_adaptive.py owns the reorder behavior)."""
    monkeypatch.setenv("IGLOO_ADAPTIVE", "0")
    res = engine.query("EXPLAIN ANALYZE " + TWO_JOIN_SQL)
    qs = res.stats
    assert qs is not None and qs.tier == "device" and qs.detail
    joins = qs.find_ops("Join")
    assert len(joins) == 2
    # every fact row matches exactly one dim row and one grp row
    assert sorted(j.rows_out for j in joins) == [400, 400]
    scans = qs.find_ops("Scan")
    assert {s.rows_out for s in scans} >= {400, 40, 4}
    aggs = qs.find_ops("Aggregate")
    assert aggs and aggs[0].rows_out == 4
    # compile time observed somewhere in the tree (cold programs)
    assert qs.compile_s > 0
    text = "\n".join(res.table.column("plan").to_pylist())
    assert "actual (operator tree)" in text and "rows=400" in text \
        and "tier=device" in text


def test_plain_select_stats_no_syncs(engine):
    """Default collection: tier + totals + tree present, rows from the
    result only (no per-op device syncs), transfer bytes recorded."""
    res = engine.query(TWO_JOIN_SQL)
    qs = res.stats
    assert qs is not None and qs.tier == "device"
    assert qs.rows == 4 and qs.elapsed_s > 0
    assert qs.h2d_bytes > 0  # cold scan uploads
    assert qs.d2h_bytes > 0  # result fetch
    assert not qs.detail
    # fused path: one program node, no per-operator children
    assert qs.find_ops("FusedProgram")
    rec = qs.to_record()
    assert rec["tier"] == "device" and rec["rows"] == 4
    assert rec["h2d_bytes"] == qs.h2d_bytes


def test_result_cache_tier(engine):
    engine.query(TWO_JOIN_SQL)
    res = engine.query(TWO_JOIN_SQL)
    assert res.stats.tier == "result_cache"


def test_chunked_tier_attribution():
    t = pa.table({"a": pa.array(list(range(20_000)), type=pa.int64()),
                  "v": pa.array([float(i % 9) for i in range(20_000)])})
    e = QueryEngine(chunk_budget_bytes=max(t.nbytes // 3, 1))
    e.register_table("big", MemTable(t, partitions=8))
    res = e.query("SELECT sum(v) AS s, count(*) AS n FROM big")
    assert res.stats.tier == "chunked"
    assert res.table.column("n").to_pylist() == [20_000]
    chunked = res.stats.find_ops("ChunkedExecution")
    assert chunked and chunked[0].attrs["chunks"] >= 3
    # per-chunk rows are host Arrow counts — free, recorded at default level
    chunk_ops = res.stats.find_ops("Chunk[")
    assert chunk_ops and all(c.rows_out is not None for c in chunk_ops)


@pytest.fixture(scope="module")
def grace_tables(tmp_path_factory):
    d = tmp_path_factory.mktemp("telemetry_grace")
    rng = np.random.default_rng(7)
    n_fact, n_dim = 12_000, 400
    fact = pa.table({
        "fk": pa.array(rng.integers(1, n_dim + 1, n_fact), type=pa.int64()),
        "v": np.round(rng.random(n_fact) * 100, 2),
    })
    dim = pa.table({
        "k": pa.array(np.arange(1, n_dim + 1), type=pa.int64()),
        "g": pa.array((np.arange(n_dim) % 5).astype(np.int64)),
    })
    pq.write_table(fact, os.path.join(d, "fact.parquet"),
                   row_group_size=2000)
    pq.write_table(dim, os.path.join(d, "dim.parquet"), row_group_size=100)
    return d, fact, dim


def test_grace_tier_partition_rollup(grace_tables):
    """EXPLAIN ANALYZE through the GRACE tier: tier attribution, partition
    rollup attrs on the GraceJoin node, per-phase children, actual rows on
    the first partitions' operator subtrees."""
    from igloo_tpu.connectors.parquet import ParquetTable
    d, fact, dim = grace_tables
    e = QueryEngine(chunk_budget_bytes=48 << 10)
    e.register_table("fact", ParquetTable(os.path.join(d, "fact.parquet")))
    e.register_table("dim", ParquetTable(os.path.join(d, "dim.parquet")))
    res = e.query("EXPLAIN ANALYZE SELECT g, sum(v) AS s FROM fact "
                  "JOIN dim ON fk = k GROUP BY g ORDER BY g")
    qs = res.stats
    assert qs.tier == "grace"
    gj = qs.find_ops("GraceJoin")
    assert gj and gj[0].attrs["partitions"] >= 2
    assert gj[0].attrs["partitions_run"] >= 1
    assert "partition_rows" in gj[0].attrs and "partition_ms" in gj[0].attrs
    phases = {o.name for o in qs.ops() if o.name.startswith("GracePhase")}
    assert phases == {"GracePhase(partition)", "GracePhase(join)",
                      "GracePhase(merge)"}
    parts = qs.find_ops("Partition[")
    assert parts  # detail mode keeps the first partitions' subtrees
    assert any(o.name.startswith("Join") and o.rows_out is not None
               for p in parts for o in p.walk())
    text = "\n".join(res.table.column("plan").to_pylist())
    assert "GraceJoin" in text and "grace.partitions:" in text
    # answer correctness against the in-memory path
    e2 = QueryEngine()
    e2.register_table("fact", fact)
    e2.register_table("dim", dim)
    expect = e2.execute("SELECT g, sum(v) AS s FROM fact JOIN dim "
                        "ON fk = k GROUP BY g ORDER BY g")
    got = e.execute("SELECT g, sum(v) AS s FROM fact JOIN dim "
                    "ON fk = k GROUP BY g ORDER BY g")
    assert got.column("g").to_pylist() == expect.column("g").to_pylist()
    assert np.allclose(got.column("s").to_pylist(),
                       expect.column("s").to_pylist())


def test_system_tables_roundtrip(engine):
    engine.execute(TWO_JOIN_SQL)
    log = engine.execute("SELECT * FROM system.query_log")
    assert log.num_rows >= 1
    sqls = log.column("sql").to_pylist()
    assert any("JOIN grp" in s for s in sqls)
    row = {name: log.column(name)[log.num_rows - 1].as_py()
           for name in log.schema.names}
    assert row["tier"] in ("device", "result_cache")
    assert row["elapsed_s"] > 0
    m = engine.execute("SELECT * FROM system.metrics")
    names = m.column("name").to_pylist()
    kinds = m.column("kind").to_pylist()
    vals = dict(zip(zip(names, kinds), m.column("value").to_pylist()))
    assert vals[("jit.miss", "counter")] > 0
    assert vals[("query.latency_s", "hist_count")] >= 1
    # live telemetry: the metrics query ITSELF changes counters, so a
    # repeated read must not be served stale from the result cache
    m2 = engine.execute("SELECT * FROM system.metrics")
    v2 = {(n, k): v for n, k, v in zip(
        m2.column("name").to_pylist(), m2.column("kind").to_pylist(),
        m2.column("value").to_pylist())}
    assert v2[("query.latency_s", "hist_count")] > \
        vals[("query.latency_s", "hist_count")]
    # system tables stay out of SHOW TABLES and survive DROP attempts
    shown = engine.execute("SHOW TABLES").column("table_name").to_pylist()
    assert "system.metrics" not in shown and "metrics" not in shown
    from igloo_tpu.errors import IglooError
    with pytest.raises(IglooError):
        engine.execute("DROP TABLE system.metrics")
    # the namespace is read-only: registration cannot shadow live telemetry
    with pytest.raises(IglooError):
        engine.register_table("system.metrics",
                              pa.table({"x": [1]}))
    assert engine.execute("SELECT count(*) FROM system.metrics").num_rows == 1


def test_query_log_jsonl_export(engine, tmp_path, monkeypatch):
    path = tmp_path / "qlog.jsonl"
    monkeypatch.setenv("IGLOO_QUERY_LOG", str(path))
    engine.execute("SELECT count(*) FROM fact")
    lines = path.read_text().strip().splitlines()
    assert lines
    rec = json.loads(lines[-1])
    assert rec["sql"].startswith("SELECT count(*)")
    assert {"tier", "rows", "elapsed_s", "h2d_bytes"} <= set(rec)


def test_counter_delta_isolation_two_threads():
    """Two threads inside their own counter_delta() each observe ONLY their
    own bumps — the footgun the snapshot-diff pattern had."""
    start = threading.Barrier(2)
    deltas = {}

    def work(tag, other):
        with tracing.counter_delta() as d:
            start.wait()
            for _ in range(50):
                tracing.counter(f"test.iso_{tag}")
                tracing.counter("test.iso_shared")
            deltas[tag] = d
    t1 = threading.Thread(target=work, args=("a", "b"))
    t2 = threading.Thread(target=work, args=("b", "a"))
    t1.start(); t2.start(); t1.join(); t2.join()
    for tag, other in (("a", "b"), ("b", "a")):
        assert deltas[tag].get(f"test.iso_{tag}") == 50
        assert deltas[tag].get(f"test.iso_{other}") == 0
        assert deltas[tag].get("test.iso_shared") == 50  # not 100
    # process-wide totals still cumulative
    assert tracing.counters().get("test.iso_shared", 0) >= 100


def test_counter_delta_nesting_and_adoption():
    with tracing.counter_delta() as outer:
        tracing.counter("test.nest", 2)
        with tracing.counter_delta() as inner:
            tracing.counter("test.nest", 3)
        ctx = stats.capture()

        def worker():
            with stats.adopt(ctx):
                tracing.counter("test.nest", 5)
        t = threading.Thread(target=worker)
        t.start(); t.join()
    assert inner.get("test.nest") == 3
    assert outer.get("test.nest") == 10  # 2 + 3 + adopted 5


def test_span_roots_bounded_and_last_trace_arg():
    tracing.reset()
    for i in range(tracing.ROOTS_MAX + 10):
        with tracing.span(f"s{i}"):
            pass
    assert len(tracing.roots()) == tracing.ROOTS_MAX
    assert tracing.last_trace(3).count("\n") == 2  # 3 roots, one line each
    assert "s1:" not in tracing.last_trace(2)


def test_prometheus_text():
    tracing.counter("test.prom_counter", 7)
    tracing.histogram("test.prom_hist", 1.5)
    tracing.histogram("test.prom_hist", 2.5)
    text = tracing.prometheus_text(extra_lines=["extra_metric 1"])
    assert "# TYPE igloo_test_prom_counter_total counter" in text
    assert "igloo_test_prom_counter_total" in text
    assert "igloo_test_prom_hist_count 2" in text
    assert "igloo_test_prom_hist_sum 4.0" in text
    assert text.rstrip().endswith("extra_metric 1")


def test_coordinator_prometheus_aggregation():
    """DistributedExecutor folds per-fragment worker stats into labeled
    Prometheus series (unit-level: no sockets in tier-1)."""
    from igloo_tpu.cluster.coordinator import DistributedExecutor, Membership
    ex = DistributedExecutor(Membership())
    ex._accumulate({"fragments": [
        {"id": "f1", "worker": "w1", "rows": 100, "elapsed_s": 0.5,
         "dispatch_s": 0.1, "dep_fetch_s": 0.0, "h2d_bytes": 1024,
         "d2h_bytes": 64, "jit_misses": 2},
        {"id": "f2", "worker": "w1", "rows": 50, "elapsed_s": 0.25,
         "dispatch_s": 0.05, "dep_fetch_s": 0.01, "h2d_bytes": 0,
         "d2h_bytes": 0, "jit_misses": 0},
        {"id": "f3", "worker": "w2", "rows": 7, "elapsed_s": 0.1},
    ]})
    lines = ex.prometheus_lines()
    text = "\n".join(lines)
    assert 'igloo_coordinator_worker_fragments_total{worker="w1"} 2' in text
    assert 'igloo_coordinator_worker_fragments_total{worker="w2"} 1' in text
    assert 'igloo_coordinator_worker_fragment_rows_total{worker="w1"} 150' in text
    assert 'igloo_coordinator_worker_fragment_h2d_bytes_total{worker="w1"} 1024' in text


def test_metrics_name_lint_passes():
    """The verify-flow lint itself: code names match the documented catalog
    (now the metric-names checker inside igloo-lint; tests/test_lint.py
    covers the other rules and the fixtures)."""
    from igloo_tpu.lint import run_lint
    findings, _warnings = run_lint(select={"metric-names"})
    assert findings == [], "\n".join(f.render() for f in findings)
