"""Chunked (partition-at-a-time) execution: tables larger than the per-batch
budget stream through partial fragments instead of materializing whole
(VERDICT round-2 item 6; reference analog: streaming 1024-row read batches,
parquet_scan.rs:54, never exploited for memory-bounded aggregation)."""
import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from igloo_tpu.catalog import MemTable
from igloo_tpu.engine import QueryEngine


@pytest.fixture(scope="module")
def big(tmp_path_factory):
    rng = np.random.default_rng(5)
    n = 20000
    t = pa.table({
        "k": rng.integers(0, 25, n),
        "s": pa.array([f"cat{i % 6}" for i in range(n)]),
        "v": rng.random(n),
        "q": rng.integers(1, 100, n).astype(np.int64),
    })
    path = str(tmp_path_factory.mktemp("chunk") / "big.parquet")
    pq.write_table(t, path, row_group_size=1500)  # 14 row groups
    return path, t


def _engines(path, budget):
    from igloo_tpu.connectors.parquet import ParquetTable
    chunked = QueryEngine(chunk_budget_bytes=budget)
    chunked.register_table("t", ParquetTable(path))
    plain = QueryEngine()  # default budget: no chunking for this size
    plain.register_table("t", ParquetTable(path))
    return chunked, plain


def _same(a, b):
    import pandas as pd
    pd.testing.assert_frame_equal(a.to_pandas().reset_index(drop=True),
                                  b.to_pandas().reset_index(drop=True),
                                  check_dtype=False, atol=1e-9)


def test_chunking_triggers(big):
    path, t = big
    from igloo_tpu.connectors.parquet import ParquetTable
    from igloo_tpu.exec.chunked import chunk_count
    eng = QueryEngine(chunk_budget_bytes=1 << 16)  # 64 KiB << table size
    eng.register_table("t", ParquetTable(path))
    plan = eng.plan("SELECT s, SUM(v) AS sv FROM t GROUP BY s")
    n = chunk_count(plan, eng.chunk_budget_bytes)
    assert n >= 4  # table is several times the budget
    # a non-streamable plan (bare sort) must NOT route to the chunked path
    plan2 = eng.plan("SELECT k, v FROM t ORDER BY v LIMIT 5")
    assert chunk_count(plan2, eng.chunk_budget_bytes) == 0
    # a distinct aggregate streams: the optimizer splits it, and its first
    # stage (grouped by k) is a decomposable aggregate over the scan
    plan3 = eng.plan("SELECT s, COUNT(DISTINCT k) AS d, SUM(v) AS sv "
                     "FROM t GROUP BY s")
    assert chunk_count(plan3, eng.chunk_budget_bytes) >= 4


@pytest.mark.parametrize("sql", [
    "SELECT s, SUM(v) AS sv, COUNT(*) AS c, AVG(v) AS av, MIN(q) AS mn, "
    "MAX(q) AS mx FROM t GROUP BY s ORDER BY s",
    "SELECT COUNT(*) AS c, SUM(v * q) AS sv FROM t WHERE v > 0.5",
    "SELECT k, COUNT(*) AS c FROM t WHERE s <> 'cat0' GROUP BY k ORDER BY k",
    # a DISTINCT aggregate: its split's first stage streams
    "SELECT s, COUNT(DISTINCT k) AS dk, SUM(v) AS sv FROM t GROUP BY s "
    "ORDER BY s",
    # bare sort/limit: routing sends this down the NORMAL path (chunking a
    # non-aggregate pipeline would union everything back — module docstring)
    "SELECT k, v FROM t ORDER BY v DESC LIMIT 9",
])
def test_chunked_matches_whole_table(big, sql):
    path, _ = big
    chunked, plain = _engines(path, 1 << 16)
    _same(chunked.execute(sql), plain.execute(sql))


def test_chunked_join_with_small_side(big):
    path, _ = big
    chunked, plain = _engines(path, 1 << 16)
    dim = pa.table({"k": np.arange(25), "name": [f"n{i}" for i in range(25)]})
    for e in (chunked, plain):
        e.register_table("d", MemTable(dim))
    sql = ("SELECT d.name, SUM(t.v) AS sv FROM t JOIN d ON t.k = d.k "
           "GROUP BY d.name ORDER BY d.name")
    _same(chunked.execute(sql), plain.execute(sql))


def test_chunk_cap_derived_from_budget(big):
    """The chunk count is derived from the budget, not capped at 64; when the
    provider cannot split finely enough to bound per-chunk memory, the clamp
    is reported via the chunked.chunks_clamped counter instead of silently
    un-bounding."""
    from igloo_tpu.connectors.parquet import ParquetTable
    from igloo_tpu.exec.chunked import chunk_count, estimated_lane_bytes
    from igloo_tpu.utils import tracing
    path, _ = big
    eng = QueryEngine()
    eng.register_table("t", ParquetTable(path))
    plan = eng.plan("SELECT s, SUM(v) AS sv FROM t GROUP BY s")
    prov = eng.catalog.get("t")
    # the price of the plan's scan: the two columns it reads, not the file
    nbytes = estimated_lane_bytes(prov, ["s", "v"])
    assert nbytes < estimated_lane_bytes(prov)
    parts = prov.num_partitions()  # 14 row groups
    # budget small enough that the NEED exceeds the provider's partitions:
    # the count clamps to `parts` and the warning counter fires
    tracing.reset_counters()
    assert chunk_count(plan, nbytes // (parts * 4)) == parts
    assert tracing.counters().get("chunked.chunks_clamped", 0) == 1
    # a budget the provider CAN honor derives the exact need, un-clamped
    tracing.reset_counters()
    budget = -(-nbytes // (parts - 2))
    assert chunk_count(plan, budget) == parts - 2
    assert not tracing.counters().get("chunked.chunks_clamped")


def test_memtable_chunking():
    rng = np.random.default_rng(9)
    n = 5000
    t = pa.table({"g": [f"x{i % 3}" for i in range(n)], "v": rng.random(n)})
    eng = QueryEngine(chunk_budget_bytes=1 << 12)
    eng.register_table("m", MemTable(t, partitions=8))
    got = eng.execute("SELECT g, SUM(v) AS sv FROM m GROUP BY g ORDER BY g")
    want = t.to_pandas().groupby("g").v.sum()
    np.testing.assert_allclose(got.column("sv").to_pylist(), want.values,
                               rtol=1e-9)
