"""Out-of-core GRACE execution (exec/grace.py).

v1 coverage (slow, parquet-backed): a single over-budget join executes
partition-pair at a time and matches the in-memory answer.

v2 coverage (fast, tier-1): multi-join TPC-H-shaped plans (Q3/Q5/Q18) under a
~1 MB budget route through the generalized planner and match the in-memory
path; string partition keys hash host-side; a two-fact plan recurses GRACE
inside partitions; and the double-buffered pipeline produces results identical
to the serial loop."""
import os

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from igloo_tpu.engine import QueryEngine
from igloo_tpu.utils import tracing


@pytest.fixture(scope="module")
def parquet_tables(tmp_path_factory):
    d = tmp_path_factory.mktemp("grace")
    rng = np.random.default_rng(13)
    n_fact, n_dim = 40_000, 2_000
    fact = pa.table({
        "fk": pa.array(rng.integers(1, n_dim + 1, n_fact), type=pa.int64()),
        "v": np.round(rng.random(n_fact) * 100, 2),
        "tag": pa.array((rng.integers(0, 5, n_fact)).astype(np.int64)),
    })
    dim = pa.table({
        "k": pa.array(np.arange(1, n_dim + 1), type=pa.int64()),
        "w": np.round(rng.random(n_dim) * 10, 2),
    })
    # several row groups so phase 1 reads provider-partition at a time
    pq.write_table(fact, os.path.join(d, "fact.parquet"), row_group_size=5000)
    pq.write_table(dim, os.path.join(d, "dim.parquet"), row_group_size=500)
    return d, fact, dim


def _mk_engine(d, budget):
    e = QueryEngine(chunk_budget_bytes=budget)
    from igloo_tpu.connectors.parquet import ParquetTable
    e.register_table("fact", ParquetTable(os.path.join(d, "fact.parquet")))
    e.register_table("dim", ParquetTable(os.path.join(d, "dim.parquet")))
    return e


AGG_SQL = """
    SELECT tag, count(*) AS n, sum(v * w) AS s, avg(v) AS a
    FROM fact JOIN dim ON fk = k
    WHERE v > 5 GROUP BY tag ORDER BY tag
"""
PLAIN_SQL = """
    SELECT fk, v, w FROM fact JOIN dim ON fk = k
    WHERE v > 98 ORDER BY fk, v
"""


def test_grace_join_agg_matches_in_memory(parquet_tables):
    d, fact, dim = parquet_tables
    want = _mk_engine(d, 1 << 40).execute(AGG_SQL)  # huge budget: normal path

    # tiny budget: force multi-partition grace execution
    e = _mk_engine(d, 64 << 10)
    tracing.reset_counters()
    got = e.execute(AGG_SQL)
    assert tracing.counters().get("engine.grace_route", 0) == 1
    assert tracing.counters().get("grace.join", 0) == 1
    assert got.column("tag").to_pylist() == want.column("tag").to_pylist()
    assert got.column("n").to_pylist() == want.column("n").to_pylist()
    np.testing.assert_allclose(got.column("s").to_pylist(),
                               want.column("s").to_pylist(), rtol=1e-9)
    np.testing.assert_allclose(got.column("a").to_pylist(),
                               want.column("a").to_pylist(), rtol=1e-9)


def test_grace_join_no_aggregate(parquet_tables):
    d, fact, dim = parquet_tables
    want = _mk_engine(d, 1 << 40).execute(PLAIN_SQL)
    e = _mk_engine(d, 64 << 10)
    tracing.reset_counters()
    got = e.execute(PLAIN_SQL)
    assert tracing.counters().get("engine.grace_route", 0) == 1
    assert got.to_pydict() == want.to_pydict()


def test_small_budget_non_join_still_normal(parquet_tables):
    d, _, _ = parquet_tables
    e = _mk_engine(d, 64 << 10)
    tracing.reset_counters()
    out = e.execute("SELECT count(*) AS c FROM dim")
    assert out.column("c")[0].as_py() == 2000
    assert not tracing.counters().get("engine.grace_route")


# --- GRACE v2: multi-join trees, string keys, recursion, pipelining ---------


@pytest.fixture(scope="module")
def tpch_small():
    from igloo_tpu.bench.tpch import gen_tables
    return gen_tables(sf=0.01, seed=11)


@pytest.fixture(scope="module")
def tpch_in_memory(tpch_small):
    """Reference engine: huge budget, everything executes in-memory."""
    from igloo_tpu.bench.tpch import register_all
    e = QueryEngine(chunk_budget_bytes=1 << 40)
    register_all(e, tpch_small)
    return e


def _tpch_engine(tables, budget=1 << 20):
    from igloo_tpu.bench.tpch import register_all
    e = QueryEngine(chunk_budget_bytes=budget)
    register_all(e, tables)
    return e


def _assert_tables_match(got: pa.Table, want: pa.Table):
    """Exact for keys/counts/strings; float aggregates compare to 1e-9 (the
    merge sums per-partition partials, so the summation order differs)."""
    assert got.num_rows == want.num_rows
    assert got.column_names == want.column_names
    for name in got.column_names:
        a, b = got.column(name).to_pylist(), want.column(name).to_pylist()
        if pa.types.is_floating(got.schema.field(name).type):
            np.testing.assert_allclose(a, b, rtol=1e-9, err_msg=name)
        else:
            assert a == b, name


@pytest.mark.parametrize("qid", ["q3", "q5"])
def test_grace_v2_tpch_smoke(tpch_small, tpch_in_memory, qid):
    """Tier-1 out-of-core smoke: Q3/Q5-shaped multi-join plans at SF0.01
    under a ~1 MB budget route through GRACE v2 and match in-memory."""
    from igloo_tpu.bench.tpch import QUERIES
    want = tpch_in_memory.execute(QUERIES[qid])
    e = _tpch_engine(tpch_small)
    tracing.reset_counters()
    got = e.execute(QUERIES[qid])
    c = tracing.counters()
    assert c.get("engine.grace_route", 0) == 1
    assert c.get("grace.partitions", 0) > 1
    _assert_tables_match(got, want)


def test_grace_v2_q18_semi_with_subquery_leaf(tpch_small, tpch_in_memory):
    """Q18 shape: a SEMI join whose build side is an aggregate subquery over
    the over-budget table — the subquery leaf co-partitions by its output key
    alongside orders/lineitem."""
    from igloo_tpu.bench.tpch import QUERIES
    want = tpch_in_memory.execute(QUERIES["q18"])
    e = _tpch_engine(tpch_small)
    tracing.reset_counters()
    got = e.execute(QUERIES["q18"])
    assert tracing.counters().get("engine.grace_route", 0) == 1
    _assert_tables_match(got, want)


def test_grace_string_partition_keys():
    """Dictionary-encoded string join keys hash host-side (native hash64)
    and co-partition both sides."""
    rng = np.random.default_rng(3)
    n = 60_000
    fact = pa.table({
        "skey": pa.array([f"key_{i:04d}" for i in rng.integers(0, 500, n)]),
        "v": np.round(rng.random(n) * 100, 2),
        "tag": rng.integers(0, 7, n).astype(np.int64),
    })
    dim = pa.table({
        "dkey": pa.array([f"key_{i:04d}" for i in range(500)]),
        "w": np.round(rng.random(500) * 10, 2),
    })
    sql = ("SELECT tag, count(*) AS n, sum(v * w) AS s FROM fact "
           "JOIN dim ON skey = dkey GROUP BY tag ORDER BY tag")
    big = QueryEngine()
    big.register_table("fact", fact)
    big.register_table("dim", dim)
    want = big.execute(sql)
    small = QueryEngine(chunk_budget_bytes=256 << 10)
    small.register_table("fact", fact)
    small.register_table("dim", dim)
    tracing.reset_counters()
    got = small.execute(sql)
    c = tracing.counters()
    assert c.get("engine.grace_route", 0) == 1
    assert c.get("grace.partitions", 0) > 1
    _assert_tables_match(got, want)


def test_grace_recursive_repartition():
    """Two over-budget facts joined through a bridge on DIFFERENT key
    classes: the outer level partitions one fact, and each partition re-enters
    GRACE to partition the replicated other fact."""
    rng = np.random.default_rng(4)
    n = 20_000
    f1 = pa.table({"a": rng.integers(0, 1000, n).astype(np.int64),
                   "v1": np.round(rng.random(n), 2)})
    bridge = pa.table({"ba": np.arange(1000, dtype=np.int64),
                       "bb": rng.permutation(1000).astype(np.int64)})
    f2 = pa.table({"b": rng.integers(0, 1000, n).astype(np.int64),
                   "v2": np.round(rng.random(n), 2)})
    sql = ("SELECT count(*) AS n, sum(v1 * v2) AS s FROM f1 "
           "JOIN bridge ON a = ba JOIN f2 ON bb = b")

    def mk(budget):
        e = QueryEngine(chunk_budget_bytes=budget)
        for nm, t in (("f1", f1), ("bridge", bridge), ("f2", f2)):
            e.register_table(nm, t)
        return e

    want = mk(1 << 40).execute(sql)
    tracing.reset_counters()
    got = mk(96 << 10).execute(sql)
    c = tracing.counters()
    assert c.get("engine.grace_route", 0) == 1
    assert c.get("grace.recursive", 0) >= 1
    _assert_tables_match(got, want)


def test_grace_anti_join_subquery():
    """ANTI joins distribute over co-partitioned buckets only when the probe
    side is anchored; an empty build bucket must still run its partition (the
    probe rows pass through)."""
    rng = np.random.default_rng(12)
    n = 30_000
    a = pa.table({"z": rng.integers(0, 500, n).astype(np.int64),
                  "x": rng.integers(0, 800, n).astype(np.int64),
                  "va": np.round(rng.random(n), 2)})
    b = pa.table({"y": rng.integers(0, 800, n).astype(np.int64),
                  "vb": np.round(rng.random(n), 2)})
    c = pa.table({"k": np.arange(0, 1000, dtype=np.int64),
                  "w": np.round(rng.random(1000), 2)})
    sql = ("SELECT count(*) AS n, sum(w) AS sw FROM c WHERE NOT EXISTS "
           "(SELECT 1 FROM a JOIN b ON x = y WHERE z = k AND va + vb > 1.6)")

    def mk(budget):
        e = QueryEngine(chunk_budget_bytes=budget)
        for nm, t in (("a", a), ("b", b), ("c", c)):
            e.register_table(nm, t)
        return e

    want = mk(1 << 40).execute(sql)
    tracing.reset_counters()
    got = mk(128 << 10).execute(sql)
    assert tracing.counters().get("engine.grace_route", 0) == 1
    _assert_tables_match(got, want)


def test_grace_pipeline_on_off_identical(tpch_small, monkeypatch):
    """Thread-safety A/B: the double-buffered prefetch loop and the serial
    loop produce identical results (and the pipelined run actually engaged
    the prefetch thread)."""
    from igloo_tpu.bench.tpch import QUERIES
    monkeypatch.setenv("IGLOO_GRACE_PIPELINE", "0")
    tracing.reset_counters()
    serial = _tpch_engine(tpch_small).execute(QUERIES["q3"])
    assert tracing.counters().get("grace.pipeline", 0) == 0
    monkeypatch.setenv("IGLOO_GRACE_PIPELINE", "1")
    tracing.reset_counters()
    piped = _tpch_engine(tpch_small).execute(QUERIES["q3"])
    c = tracing.counters()
    assert c.get("engine.grace_route", 0) == 1
    assert c.get("grace.pipeline", 0) >= 1
    assert piped.to_pydict() == serial.to_pydict()


def test_grace_partition_count_derived_from_budget(tpch_small):
    """The partition count comes from ceil(partitionable bytes / budget) —
    no silent 64 cap — and only the sanity clamp (with a warning counter)
    bounds it."""
    from igloo_tpu.bench.tpch import QUERIES
    from igloo_tpu.exec.grace import (
        MAX_GRACE_PARTITIONS, find_grace_join,
    )
    e = _tpch_engine(tpch_small)
    plan = e.plan(QUERIES["q3"])
    lineitem = tpch_small["lineitem"]
    orders = tpch_small["orders"]
    part_bytes = lineitem.nbytes + orders.nbytes
    budget = max(part_bytes // 200, 1)  # needs ~200 partitions (> old cap 64)
    gp = find_grace_join(plan, budget)
    assert gp is not None and 64 < gp.n_parts <= MAX_GRACE_PARTITIONS
    # a pathological budget trips the sanity clamp and the warning counter
    tracing.reset_counters()
    gp2 = find_grace_join(plan, 64)
    assert gp2 is not None and gp2.n_parts == MAX_GRACE_PARTITIONS
    assert tracing.counters().get("grace.partitions_clamped", 0) == 1


def test_grace_explain_analyze_phases(tpch_small):
    """EXPLAIN ANALYZE routes through the GRACE tier and surfaces the
    per-phase breakdown."""
    from igloo_tpu.bench.tpch import QUERIES
    e = _tpch_engine(tpch_small)
    res = e.query("EXPLAIN ANALYZE " + QUERIES["q3"].strip())
    text = "\n".join(res.table.column("plan").to_pylist())
    assert "grace.partitions:" in text
    assert "grace.partition_s:" in text
    assert "grace.join_s:" in text
    assert "grace.merge_s:" in text
