"""One price for a scan, one rule for "one program or chunks".

A scan is priced by the columns it reads, from the file's footer alone
(exec/chunked.py estimated_lane_bytes): the same query gets the same tier
and the same price on every execution, whatever ran between; a decomposable
aggregate over a scan is one program while the columns it reads fit the
resident share of the device (exec/cache.py hbm_budgets) and chunked when
they do not — as a worker's scan fragment on the same chip is; the
optimizer's join order and the GRACE trigger keep the whole-table size they
had (table_lane_bytes)."""
import datetime
import os
import time

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from igloo_tpu.bench import tpch, tpch_pandas
from igloo_tpu.connectors.parquet import ParquetTable
from igloo_tpu.engine import QueryEngine
from igloo_tpu.exec import codec
from igloo_tpu.exec.batch import round_capacity
from igloo_tpu.exec.chunked import (chunk_count, estimated_lane_bytes,
                                    table_lane_bytes)
from igloo_tpu.plan import logical as L
from igloo_tpu.utils import tracing

from test_hbm_budget import GB, READS, assert_answers, fake_limits

Q6_READS = ["l_quantity", "l_extendedprice", "l_discount", "l_shipdate"]


def scan_of(plan: L.LogicalPlan, table: str) -> L.Scan:
    return next(n for n in L.walk_plan(plan)
                if isinstance(n, L.Scan) and n.table == table)


@pytest.fixture(scope="module")
def sf001(tmp_path_factory):
    """SF 0.01 TPC-H as Parquet (`lineitem` in ten row groups: the chunked
    tier's unit), and the oracle's q1 / q6 on it."""
    tables = tpch.gen_tables(sf=0.01, seed=20261004)
    root = tmp_path_factory.mktemp("sf001")
    paths = {}
    for name, table in tables.items():
        paths[name] = str(root / f"{name}.parquet")
        pq.write_table(table, paths[name],
                       row_group_size=table.num_rows // 10 + 1)
    df = tables["lineitem"].select(READS).to_pandas()
    epoch = np.datetime64("1970-01-01")
    df["l_shipdate"] = ((df["l_shipdate"].to_numpy().astype("datetime64[D]")
                         - epoch).astype(np.int64))
    t = {"lineitem": df}
    return paths, tables, tpch_pandas.q1(t), tpch_pandas.q6(t)


def prices(path: str) -> tuple:
    prov = ParquetTable(path)
    return (estimated_lane_bytes(prov, READS),
            estimated_lane_bytes(prov, Q6_READS))


# --- (a) the same tier, price and answer on every execution -------------------

@pytest.mark.parametrize("where", ["between", "above"])
def test_same_tier_and_price_on_every_execution(sf001, where):
    """q1, q6, q1, q6, q1, q6 in one process, at a budget between what q6
    and q1 read and at one above both. Before, a scan was priced at its
    whole file x 3.5 x the carrier ratio the LAST scan of any query left,
    and the same q1 was chunked the first time and one program the second
    because a q6 ran between."""
    paths, _, want_q1, want_q6 = sf001
    p1, p6 = prices(paths["lineitem"])
    assert p6 < p1
    budget = (p1 + p6) // 2 if where == "between" else 2 * p1
    eng = QueryEngine(chunk_budget_bytes=budget)
    eng.register_table("lineitem", ParquetTable(paths["lineitem"]))
    want_tier = {"q1": "chunked" if where == "between" else "device",
                 "q6": "device"}
    got = {}
    for n in range(3):
        for q, price in (("q1", p1), ("q6", p6)):
            eng.result_cache.clear()
            with tracing.counter_delta() as d:
                res = eng.query(tpch.QUERIES[q])
            got[q] = res.table
            assert res.stats.tier == want_tier[q], (n, q)
            assert d.get("engine.route_priced_bytes") == price, (n, q)
            assert d.get("engine.chunked_route", 0) == \
                (want_tier[q] == "chunked"), (n, q)
            if n:
                # resident once, whichever tier: chunks and whole columns
                # alike are hits, the merge of the chunks' partial results
                # (ephemeral: keyed by position, never cached) traces nothing
                assert d.get("cache.miss", 0) == 0, (n, q)
                assert d.get("jit.miss", 0) == 0, (n, q)
                assert d.get("xfer.h2d_bytes", 0) < 64 << 10, (n, q)
        assert_answers(got["q1"], got["q6"], want_q1, want_q6)
    if where == "above":
        # one copy of the seven columns and a live lane, no chunk entries
        assert len(eng.batch_cache) == len(READS) + 1


# --- (b), (c) a price from the footer, by the columns read --------------------

def test_unread_columns_cost_nothing(tmp_path):
    rng = np.random.default_rng(3)
    n = 30_000
    read = {"k": rng.integers(0, 1 << 40, n), "v": rng.random(n)}
    wide = {f"w{i}": rng.random(n) for i in range(12)}
    pq.write_table(pa.table(read), str(tmp_path / "narrow.parquet"))
    pq.write_table(pa.table({**read, **wide}), str(tmp_path / "wide.parquet"),
                   row_group_size=10_000)
    narrow = ParquetTable(str(tmp_path / "narrow.parquet"))
    fat = ParquetTable(str(tmp_path / "wide.parquet"))
    assert estimated_lane_bytes(fat, ["k", "v"]) \
        == estimated_lane_bytes(narrow, ["k", "v"]) \
        == estimated_lane_bytes(narrow) == round_capacity(n) * (8 + 8 + 1)
    assert estimated_lane_bytes(fat) == round_capacity(n) * (14 * 8 + 1)
    # the whole-table size still follows the file
    assert table_lane_bytes(fat) > 5 * table_lane_bytes(narrow)
    eng = QueryEngine()
    eng.register_table("t", fat)
    plan = eng.plan("SELECT SUM(v) AS s, MAX(k) AS m FROM t")
    sc = scan_of(plan, "t")
    assert sorted(sc.projection) == ["k", "v"]
    price = estimated_lane_bytes(fat, sc.projection)
    assert chunk_count(plan, price) == 0
    assert chunk_count(plan, price - 1) == 2


N = 50_000
DAY0 = datetime.date(1992, 1, 1)


def _column(kind: str, rng) -> pa.Array:
    if kind == "int64 over 2^32":
        return pa.array(rng.integers(0, 1 << 40, N))
    if kind == "int64 under 100":
        return pa.array(rng.integers(1, 100, N))
    if kind == "int32 under 30000":
        return pa.array(rng.integers(0, 30_000, N).astype(np.int32))
    if kind == "int64 with nulls":
        return pa.array(rng.integers(0, 1 << 20, N),
                        mask=rng.random(N) < 0.1)
    if kind == "float64":
        return pa.array(rng.random(N) * 1e5)
    if kind == "date over 7 years":
        return pa.array([DAY0 + datetime.timedelta(days=int(d))
                         for d in rng.integers(0, 2_500, N)])
    if kind == "dictionary of 50000":
        return pa.array([f"name{i:06d}" for i in rng.permutation(N)])
    if kind == "dictionary of 3":
        return pa.array([("A", "N", "R")[i] for i in rng.integers(0, 3, N)])
    if kind == "float64 of whole numbers":
        return pa.array(rng.integers(1, 51, N).astype(np.float64))
    raise AssertionError(kind)


def _held(path: str, tmp_name: str = "t") -> tuple:
    """(the price of the scan of column `c`, the bytes its DeviceBatch
    holds)."""
    from igloo_tpu.exec.executor import Executor
    eng = QueryEngine()
    prov = ParquetTable(path)
    eng.register_table(tmp_name, prov)
    sc = scan_of(eng.plan(f"SELECT c, COUNT(*) AS n FROM {tmp_name} "
                          "GROUP BY c"), tmp_name)
    assert sc.projection in (None, ["c"])   # None: the file's one column
    batch = Executor({}, batch_cache=None)._exec_scan(sc)
    return estimated_lane_bytes(prov, sc.projection), batch.nbytes()


@pytest.mark.parametrize("kind", [
    "int64 over 2^32", "int64 under 100", "int32 under 30000",
    "int64 with nulls", "float64", "date over 7 years",
    "dictionary of 50000"])
def test_price_is_what_the_scan_holds(tmp_path, kind):
    """Within 10 % of the DeviceBatch's bytes wherever the footer decides
    the width: an integer or date column by its statistics' range, a float64
    without a narrower exact form, a dictionary's ids by the row count (a
    string a row: int16 at 50,000 rows, int32 at SF10's 60 M)."""
    path = str(tmp_path / "c.parquet")
    pq.write_table(pa.table({"c": _column(kind, np.random.default_rng(11)),
                             "other": np.arange(N)}),
                   path, row_group_size=8_000)
    price, held = _held(path)
    assert abs(price - held) <= 0.1 * held, (price, held)


@pytest.mark.parametrize("kind", ["dictionary of 3",
                                  "float64 of whole numbers"])
def test_price_never_follows_what_only_the_data_says(tmp_path, kind):
    """A dictionary of three strings ships as int8 ids and whole-number
    float64s as int8 — but only the values say so: the price stays at the
    ids 50,000 rows could need (int16) and at the lane's float64, an upper
    bound, before and after the scan (no carrier ratio of an earlier scan
    lowers it)."""
    path = str(tmp_path / "c.parquet")
    pq.write_table(pa.table({"c": _column(kind, np.random.default_rng(12))}),
                   path)
    price, held = _held(path)
    width = 2 if kind.startswith("dictionary") else 8
    assert price == round_capacity(N) * (width + 1)
    assert held == round_capacity(N) * (1 + 1)
    assert _held(path)[0] == price


def test_the_footer_is_read_once_per_file_version(tmp_path, monkeypatch):
    path = str(tmp_path / "t.parquet")
    pq.write_table(pa.table({"a": np.arange(1000)}), path)
    prov = ParquetTable(path)
    first = estimated_lane_bytes(prov, ["a"])
    opened = []
    real = pq.ParquetFile
    monkeypatch.setattr(pq, "ParquetFile",
                        lambda *a, **k: opened.append(1) or real(*a, **k))
    t0 = time.perf_counter()
    for _ in range(100):
        assert estimated_lane_bytes(prov, ["a"]) == first
    assert not opened
    assert (time.perf_counter() - t0) / 100 < 1e-3
    # a new version of the file is a new snapshot token: read again
    pq.write_table(pa.table({"a": np.arange(1 << 40, (1 << 40) + 5000)}),
                   path)
    os.utime(path, ns=(1, 1))
    assert estimated_lane_bytes(prov, ["a"]) == round_capacity(5000) * 3
    assert opened
    # a provider without such metadata keeps its conservative price
    from igloo_tpu.catalog import MemTable
    mem = MemTable(pa.table({"a": np.arange(1000), "b": np.arange(1000)}))
    assert estimated_lane_bytes(mem, ["a"]) == estimated_lane_bytes(mem) \
        == 2 * 8 * 1000


# --- (d) at the source's own scale, under a v5e's limit -----------------------

class FooterSays(ParquetTable):
    """The SF 0.01 file with a footer that states another row count."""

    def __init__(self, path: str, rows: int, parts: int):
        super().__init__(path)
        self._rows, self._nparts = rows, parts

    def lane_stats(self):
        _rows, cols = super().lane_stats()
        return self._rows, cols

    def num_partitions(self) -> int:
        return self._nparts


ALL_SIXTEEN = ("SELECT " + ", ".join(
    f"MAX({c}) AS m{i}" for i, c in enumerate(
        tpch.gen_tables(sf=0.001, seed=1)["lineitem"].column_names))
    + " FROM lineitem")


def test_sf10_q1_is_one_program_and_sf30_whole_is_chunked(monkeypatch, sf001):
    paths = sf001[0]
    fake_limits(monkeypatch, 16 * GB)
    eng = QueryEngine()
    eng.register_table("lineitem", FooterSays(paths["lineitem"],
                                              59_999_997, 58))
    assert (eng._scan_budget(), eng._chunk_budget()) == (8 * GB, 2 * GB)
    for q in ("q1", "q6"):
        plan = eng.plan(tpch.QUERIES[q])
        sc = scan_of(plan, "lineitem")
        price = estimated_lane_bytes(sc.provider, sc.projection)
        # 2^26 lanes of seven (four) columns: over the 1/8 the engine held
        # such a scan to before (q6 is not, by its columns), under the half
        # that holds them resident
        assert (1 << 26) * len(sc.projection) < price < 8 * GB
        assert (q == "q1") == (price > 2 * GB)
        assert chunk_count(plan, eng._scan_budget()) == 0
    big = QueryEngine()
    big.register_table("lineitem", FooterSays(paths["lineitem"],
                                              179_998_372, 172))
    plan = big.plan(ALL_SIXTEEN)
    sc = scan_of(plan, "lineitem")
    assert sc.projection is None and len(sc.schema) == 16
    price = estimated_lane_bytes(sc.provider, sc.projection)
    assert price > 8 * GB
    assert chunk_count(plan, big._scan_budget()) == -(-price // (8 * GB)) >= 3
    # a number given bounds the scan under the same rule
    assert chunk_count(plan, 1 * GB) == -(-price // (1 * GB))


# --- (e) engine and worker decide alike ---------------------------------------

def test_engine_and_worker_fragment_decide_alike(monkeypatch, sf001):
    """Under one faked limit — the q1 columns' price fits its resident half
    and not its eighth — the in-process engine runs q1 as one program over
    resident columns, as the worker's scan fragment (which has no ladder in
    front of it) does: the same cache entries, no chunked route."""
    from igloo_tpu.cluster.client import DistributedClient
    from igloo_tpu.cluster.coordinator import CoordinatorServer
    from igloo_tpu.cluster.worker import Worker
    paths, _, want_q1, want_q6 = sf001
    p1, _p6 = prices(paths["lineitem"])
    limit = 4 * p1
    eng = QueryEngine()
    eng.register_table("lineitem", ParquetTable(paths["lineitem"]))
    coord = CoordinatorServer("grpc+tcp://127.0.0.1:0", worker_timeout_s=60.0)
    caddr = f"127.0.0.1:{coord.port}"
    worker = Worker(caddr, port=0, heartbeat_interval_s=0.5)
    scan_cache = worker.server._batch_cache
    with monkeypatch.context() as m:
        fake_limits(m, limit)
        assert scan_cache.budget_bytes == eng.batch_cache.budget_bytes \
            == eng._scan_budget() == limit // 2
        assert eng._chunk_budget() == limit // 8 < p1
    client = None
    try:
        worker.start()
        deadline = time.time() + 20
        while not coord.membership.live() and time.time() < deadline:
            time.sleep(0.05)
        assert coord.membership.live()
        coord.register_table("lineitem", ParquetTable(paths["lineitem"]))
        client = DistributedClient(caddr)
        before = tracing.counters()
        served = {q: client.execute(tpch.QUERIES[q]) for q in ("q1", "q6")}
        assert len(client.last_metrics()["fragments"]) == 2
        local = {q: eng.query(tpch.QUERIES[q]) for q in ("q1", "q6")}
        after = tracing.counters()
    finally:
        if client is not None:
            client.close()
        worker.shutdown()
        coord.shutdown()
    moved = {k: after[k] - before.get(k, 0) for k in after}
    assert not moved.get("engine.chunked_route")
    assert not moved.get("cache.too_large") and not moved.get("cache.evict")
    assert [r.stats.tier for r in local.values()] == ["device", "device"]
    assert_answers(served["q1"], served["q6"], want_q1, want_q6)
    assert_answers(local["q1"].table, local["q6"].table, want_q1, want_q6)
    # both hold the seven columns and a live lane once, and nothing else
    # (the worker's merge inputs are ephemeral: never cached)
    assert len(eng.batch_cache) == len(scan_cache) == len(READS) + 1
    assert eng.batch_cache.nbytes == scan_cache.nbytes


# --- (f) admission reserves the columns, not the file -------------------------

def test_predict_hbm_bytes_is_twice_the_columns_read(sf001):
    from igloo_tpu.cluster import serving
    paths = sf001[0]
    prov = ParquetTable(paths["lineitem"])
    eng = QueryEngine()
    eng.register_table("lineitem", prov)
    p1, p6 = prices(paths["lineitem"])
    assert serving.predict_hbm_bytes(eng.plan(tpch.QUERIES["q6"])) == 2 * p6
    assert serving.predict_hbm_bytes(eng.plan(tpch.QUERIES["q1"])) == 2 * p1
    # sixteen columns' worth of file, which is what was reserved before
    assert 2 * p6 < table_lane_bytes(prov) < 2 * estimated_lane_bytes(prov)


# --- the optimizer keeps the whole-table size it had --------------------------

def _join_shape(plan: L.LogicalPlan) -> list:
    def scans(p):
        return sorted(s.table for s in L.walk_plan(p) if isinstance(s, L.Scan))
    return [(scans(n.left), scans(n.right)) for n in L.walk_plan(plan)
            if isinstance(n, L.Join)]


def test_join_order_of_q3_and_q5_is_the_parents(sf001):
    """`plan/optimizer.py` compares tables with each other by
    `table_lane_bytes` (file x expansion x the measured carrier ratio), as
    before the scan price existed: the orders below are what the parent of
    the PR that split the two gives on the same files, and the size follows
    the ratio a scan leaves as it did."""
    from igloo_tpu.plan.optimizer import (_est_subtree_lane_bytes,
                                          last_adaptive_decisions)
    paths = sf001[0]
    eng = QueryEngine()
    for name, path in paths.items():
        eng.register_table(name, ParquetTable(path))
    codec.reset_carrier_ratios()
    want_q3 = [(["customer", "orders"], ["lineitem"]),
               (["customer"], ["orders"])]
    want_q5 = [(["customer", "nation", "orders", "region", "supplier"],
                ["lineitem"]),
               (["customer", "nation", "region", "supplier"], ["orders"]),
               (["nation", "region", "supplier"], ["customer"]),
               (["nation", "region"], ["supplier"]),
               (["region"], ["nation"])]
    assert _join_shape(eng.plan(tpch.QUERIES["q3"])) == want_q3
    assert last_adaptive_decisions() == []
    assert _join_shape(eng.plan(tpch.QUERIES["q5"])) == want_q5
    assert [d["join_order"] for d in last_adaptive_decisions()] \
        == [[5, 4, 3, 0, 1, 2]]
    prov = eng.catalog.get("lineitem")
    scan = scan_of(eng.plan("SELECT COUNT(*) AS n FROM lineitem"), "lineitem")
    for ratio_left in (False, True):
        assert (codec.carrier_ratio(prov) < 1.0) == ratio_left
        assert _est_subtree_lane_bytes(scan) == table_lane_bytes(prov) == int(
            os.path.getsize(paths["lineitem"]) * 3.5
            * codec.carrier_ratio(prov))
        eng.execute(tpch.QUERIES["q6"])     # leaves its carrier ratio behind
