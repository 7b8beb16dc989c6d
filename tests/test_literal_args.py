"""A literal is an argument of a program and no part of a resident column's
name (ISSUE 34).

TPC-H's performance test runs each query with substitution parameters
(rev. 3, clauses 2.4.1.3 Q1, 2.4.3.3 Q3, 2.4.6.3 Q6; clause 5.3: a set per
stream). Held here, on the CPU at SF 0.01, embedded and through coordinator
+ worker: every parameter set's answer equals the plain reference's
(`benchmark/oracle/tpch_pandas_params.py`), so no cache shares a RESULT
between two sets; and from the second set on nothing is traced, compiled,
missed in the scan cache or uploaded, so every cache shares the PROGRAM and
the COLUMNS. Then the corners of the rule: what is shape (a literal's dtype
and position, a string, NULL, a function's literal argument, LIMIT) and what
is value."""
import datetime as _dt
import importlib.util
import os
import sys
import time

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from igloo_tpu.connectors.parquet import ParquetTable
from igloo_tpu.engine import QueryEngine
from igloo_tpu.exec import fused as F
from igloo_tpu.utils import tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TABLES = ("customer", "orders", "lineitem")

Q1 = """SELECT l_returnflag, l_linestatus,
       sum(l_quantity) AS sum_qty, sum(l_extendedprice) AS sum_base_price,
       sum(l_extendedprice * (1 - l_discount)) AS sum_disc_price,
       sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) AS sum_charge,
       avg(l_quantity) AS avg_qty, avg(l_extendedprice) AS avg_price,
       avg(l_discount) AS avg_disc, count(*) AS count_order
FROM lineitem
WHERE l_shipdate <= DATE '1998-12-01' - INTERVAL '{0}' DAY
GROUP BY l_returnflag, l_linestatus ORDER BY l_returnflag, l_linestatus"""
Q3 = """SELECT l_orderkey, sum(l_extendedprice * (1 - l_discount)) AS revenue,
       o_orderdate, o_shippriority
FROM customer, orders, lineitem
WHERE c_mktsegment = '{0}' AND c_custkey = o_custkey
  AND l_orderkey = o_orderkey
  AND o_orderdate < DATE '{1}' AND l_shipdate > DATE '{1}'
GROUP BY l_orderkey, o_orderdate, o_shippriority
ORDER BY revenue DESC, o_orderdate LIMIT 10"""
Q6 = """SELECT sum(l_extendedprice * l_discount) AS revenue FROM lineitem
WHERE l_shipdate >= DATE '{0}-01-01'
  AND l_shipdate < DATE '{0}-01-01' + INTERVAL '1' YEAR
  AND l_discount BETWEEN {1:.2f} AND {2:.2f} AND l_quantity < {3}"""

# the first set of each is clause 2.4's validation set, what every cell of
# the benchmark ran before this issue
Q1_SETS = [(90,), (68,), (75,), (110,)]
Q6_SETS = [(1994, 0.06, 24), (1996, 0.03, 25), (1995, 0.06, 24),
           (1993, 0.09, 25), (1997, 0.02, 24), (1994, 0.05, 25)]
# the second differs by DATE alone (by five days: a fragment's result keeps
# its capacity class, which is shape: served q3 under 03-28 re-traces the
# join fragment for a smaller `orders` result), the third by SEGMENT too (a
# string: it stays in the key, so it is a program of its own)
Q3_SETS = [("BUILDING", (1995, 3, 15)), ("BUILDING", (1995, 3, 20)),
           ("MACHINERY", (1995, 3, 5))]


def _sql(q: str, params: tuple) -> str:
    if q == "q1":
        return Q1.format(*params)
    if q == "q3":
        return Q3.format(params[0], _dt.date(*params[1]).isoformat())
    year, disc, qty = params
    return Q6.format(year, disc - 0.01, disc + 0.01, qty)


@pytest.fixture(scope="module")
def oracle():
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    try:
        spec = importlib.util.spec_from_file_location(
            "tpch_pandas_params", os.path.join(
                ROOT, "benchmark", "oracle", "tpch_pandas_params.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        from compare import compare, frame
    finally:
        sys.path.pop(0)
    return mod, compare, frame


@pytest.fixture(scope="module")
def staged(tmp_path_factory, oracle):
    """SF 0.01 as Parquet files (several row groups each) + the reference's
    frames (dates as int days)."""
    from igloo_tpu.bench.tpch import gen_tables
    root = tmp_path_factory.mktemp("tpch")
    tables = gen_tables(sf=0.01, seed=34)
    for name in TABLES:
        pq.write_table(tables[name], str(root / f"{name}.parquet"),
                       row_group_size=16384)
    return str(root), {n: oracle[2](tables[n]) for n in TABLES}


def _want(oracle, frames, q: str, params: tuple):
    return getattr(oracle[0], q)(frames, *params)


def _same(oracle, got: pa.Table, want):
    err, wrong, why = oracle[1](got, want)
    assert wrong == 0 and err <= 1e-9, why


WATCHED = ("jit.miss", "compile_cache.miss", "cache.miss", "xfer.h2d_bytes",
           "program.literal_args", "program.literal_keyed",
           "program.literal_shared", "cache.shared_by_filter",
           "fused.compact_repair", "cache.hit")


def _delta(before: dict) -> dict:
    now = tracing.counters()
    return {k: now.get(k, 0) - before.get(k, 0) for k in WATCHED}


def _table_entries(cache) -> int:
    return sum(1 for k in list(cache._entries) if k[0] in TABLES)


# --- embedded ----------------------------------------------------------------

@pytest.fixture(scope="module")
def engine(staged):
    e = QueryEngine()
    for name in TABLES:
        e.register_table(name, ParquetTable(
            os.path.join(staged[0], f"{name}.parquet")))
    return e


def _run(e: QueryEngine, sql: str):
    e.result_cache.clear()
    before = dict(tracing.counters())
    res = e.query(sql)
    assert res.stats.tier == "device"
    return res.table, _delta(before)


def _settle(e: QueryEngine, sql: str):
    """The first set: cold, with adopted hints, steady."""
    for _ in range(3):
        t, c = _run(e, sql)
    assert not c["jit.miss"]
    return t


@pytest.mark.parametrize("q,sets", [("q1", Q1_SETS), ("q6", Q6_SETS),
                                    ("q3", Q3_SETS)])
def test_embedded_parameter_sets(engine, staged, oracle, q, sets):
    _same(oracle, _settle(engine, _sql(q, sets[0])),
          _want(oracle, staged[1], q, sets[0]))
    held = _table_entries(engine.batch_cache)
    for params in sets[1:]:
        t, c = _run(engine, _sql(q, params))
        _same(oracle, t, _want(oracle, staged[1], q, params))
        assert not c["cache.miss"] and not c["xfer.h2d_bytes"], (params, c)
        assert _table_entries(engine.batch_cache) == held
        assert c["program.literal_args"] and not c["compile_cache.miss"]
        if q == "q3" and params[0] != sets[0][0]:
            # another SEGMENT: a string literal is shape
            assert c["jit.miss"] and c["program.literal_keyed"] >= 2
            continue
        assert not c["jit.miss"], (params, c)
        assert c["program.literal_shared"] >= 1
        assert c["cache.shared_by_filter"]


def test_q1_and_q6_hold_each_shared_column_once(staged):
    e = QueryEngine()
    e.register_table("lineitem", ParquetTable(
        os.path.join(staged[0], "lineitem.parquet")))
    _, c6 = _run(e, _sql("q6", Q6_SETS[0]))
    _, c1 = _run(e, _sql("q1", Q1_SETS[0]))
    cols = [k[-1] for k in e.batch_cache._entries if k[-2:-1] == ("col",)]
    assert sorted(cols) == sorted(set(cols)) and len(cols) == 7
    # q1 loads the three columns q6 had not: flag, status, tax
    assert c1["cache.miss"] == 3 and c1["cache.shared_by_filter"] == 4
    assert 0 < c1["xfer.h2d_bytes"] < c6["xfer.h2d_bytes"]


def test_a_result_is_never_shared_between_two_literal_sets(engine, staged,
                                                           oracle):
    # the result cache is keyed by the serialized plan, values included
    engine.result_cache.clear()
    for params in Q6_SETS[:3] + Q6_SETS[:1]:
        t = engine.query(_sql("q6", params)).table
        _same(oracle, t, _want(oracle, staged[1], "q6", params))


# --- the corners of the rule -------------------------------------------------

@pytest.fixture
def facts():
    e = QueryEngine()
    rng = np.random.default_rng(34)
    n = 3000
    df = pd.DataFrame({
        "k": rng.choice(["x", "y", "z"], n), "a": rng.integers(0, 12, n),
        "b": rng.integers(0, 12, n), "v": rng.random(n) * 10})
    e.register_table("facts", pa.Table.from_pandas(df, preserve_index=False))
    return e, df


def test_int_and_float_literal_of_one_value_are_two_programs(facts):
    e, df = facts
    t, c = _run(e, "SELECT count(*) AS n FROM facts WHERE a > 5")
    assert c["jit.miss"] == 1 and t.to_pydict()["n"] == [int((df.a > 5).sum())]
    t, c = _run(e, "SELECT count(*) AS n FROM facts WHERE a > 7")
    assert not c["jit.miss"] and t.to_pydict()["n"] == [int((df.a > 7).sum())]
    # dtype is shape: the comparison widens to float64
    t, c = _run(e, "SELECT count(*) AS n FROM facts WHERE a > 5.0")
    assert c["jit.miss"] == 1 and t.to_pydict()["n"] == [int((df.a > 5).sum())]


@pytest.mark.parametrize("second", ["SUM", "AVG"])
def test_two_case_arms_that_differ_by_a_literal_are_two_lanes(facts, second):
    # PR 33's guard: aggregate lanes are one by E.fingerprint (values in),
    # never by the shape that keys the program; and an argument that binds a
    # literal shares with nothing, or the program traced under (5, 5) — one
    # lane read twice — would answer (5, 6) with the first arm's sum
    e, df = facts
    sql = ("SELECT k, SUM(CASE WHEN a > {0} THEN v ELSE 0 END) AS s1, "
           + second + "(CASE WHEN a > {1} THEN v ELSE 0 END) AS s2 "
           "FROM facts GROUP BY k ORDER BY k")
    sets = [(5, 6, 1), (3, 9, 0)] if second == "SUM" \
        else [(5, 5, 1), (5, 6, 0), (3, 9, 0)]
    for lo, hi, miss in sets:
        t, c = _run(e, sql.format(lo, hi))
        assert c["jit.miss"] == miss, (lo, hi)
        got = t.to_pydict()
        for name, cut, how in (("s1", lo, "sum"),
                               ("s2", hi, "sum" if second == "SUM"
                                else "mean")):
            want = df.assign(w=np.where(df.a > cut, df.v, 0.0)) \
                .groupby("k").w.agg(how)
            np.testing.assert_allclose(got[name], want.to_numpy(), rtol=1e-12)


def test_equal_values_are_never_one_slot(facts):
    e, df = facts
    sql = "SELECT count(*) AS n FROM facts WHERE a > {0} AND b > {1}"
    t, c = _run(e, sql.format(5, 5))
    assert c["jit.miss"] == 1 and c["program.literal_args"] == 2
    assert t.to_pydict()["n"] == [int(((df.a > 5) & (df.b > 5)).sum())]
    t, c = _run(e, sql.format(5, 6))
    assert not c["jit.miss"] and c["program.literal_args"] == 2
    assert c["program.literal_shared"] == 1
    assert t.to_pydict()["n"] == [int(((df.a > 5) & (df.b > 6)).sum())]


@pytest.mark.parametrize("sql,keyed,rerun", [
    ("SELECT count(*) AS n FROM facts WHERE k = '{0}'", 1, ("x", "y")),
    ("SELECT count(*) AS n FROM facts WHERE k LIKE '{0}%'", 1, ("x", "y")),
    ("SELECT count(*) AS n FROM facts WHERE a IN (1, 2, {0})", 1, (3, 4)),
    ("SELECT a, NULL AS z FROM facts WHERE a > {0} ORDER BY a LIMIT 3", 1,
     (5, 6)),
    ("SELECT round(v, {0}) AS r FROM facts ORDER BY r DESC LIMIT 2", 2, (1, 2)),
], ids=["string", "like", "in_list", "limit_beside_null", "function_argument"])
def test_what_stays_in_a_key_is_counted(facts, sql, keyed, rerun):
    e, _df = facts
    first, second = rerun
    t1, c = _run(e, sql.format(first))
    assert c["jit.miss"] and c["program.literal_keyed"] >= keyed
    t2, c = _run(e, sql.format(second))
    # NULL is in the key and not counted: it has no value to change
    assert c["program.literal_keyed"] >= keyed
    if "NULL" in sql:
        assert c["program.literal_keyed"] == keyed
    # a numeric member of an IN list is an argument; every other case's
    # value sizes or selects code, and a new value is a new program
    assert bool(c["jit.miss"]) == (sql.find(" IN (") < 0
                                   and "NULL" not in sql)
    want = QueryEngine()
    want.register_table("facts", e.catalog.get("facts"))
    assert t2.to_pydict() == want.execute(sql.format(second)).to_pydict()
    assert t1.to_pydict() != t2.to_pydict() or "NULL" in sql


def test_row_groups_that_prune_differently_are_two_entries(tmp_path):
    path = str(tmp_path / "sorted.parquet")
    n = 4000
    pq.write_table(pa.table({"d": pa.array(np.arange(n), pa.int64()),
                             "v": pa.array(np.arange(n) * 0.5)}),
                   path, row_group_size=1000)
    e = QueryEngine()
    e.register_table("t", ParquetTable(path))
    sql = "SELECT sum(v) AS s, count(*) AS n FROM t WHERE d < {0}"
    got = {}
    for cut in (500, 2500, 700, 2600, 10 ** 6):
        t, c = _run(e, sql.format(cut))
        got[cut] = (t.to_pydict(), c)
        want = np.arange(min(cut, n)) * 0.5
        assert t.to_pydict() == {"s": [want.sum()], "n": [len(want)]}
    # 500 and 700 keep one row group, 2500 and 2600 three, 10^6 all four:
    # the name holds the set that survived, not the literal that pruned
    assert got[500][1]["cache.miss"] and got[2500][1]["cache.miss"]
    assert not got[700][1]["cache.miss"] and not got[2600][1]["cache.miss"]
    assert got[10 ** 6][1]["cache.miss"]
    idents = {k[1] for k in e.batch_cache._entries}
    assert len(idents) == 3 and None in idents


def test_a_hint_learnt_under_one_date_is_repaired_under_the_next(
        staged, oracle, monkeypatch):
    """q3's `joinout` hint learnt under a DATE that keeps few rows, adopted
    under one that keeps many: the compaction overflows, the flag fires, one
    repair re-run answers exactly."""
    from igloo_tpu.exec.executor import Executor
    monkeypatch.setattr(F, "ADAPTIVE_CAPACITY", 1 << 10)
    monkeypatch.setattr(Executor, "_SPECULATIVE_JOIN_BUDGET", 1 << 10)
    e = QueryEngine()
    for name in TABLES:
        e.register_table(name, ParquetTable(
            os.path.join(staged[0], f"{name}.parquet")))
    # DATE outside the clause's March 1995, to make the hint small: few
    # orders before it, so few joined rows
    few, many = ("BUILDING", (1992, 1, 20)), ("BUILDING", (1995, 3, 15))
    for _ in range(3):
        t, c = _run(e, _sql("q3", few))
    _same(oracle, t, _want(oracle, staged[1], "q3", few))
    hints = {k: v for k, v in e._jit_cache.items() if k[0] == "nhint"}
    assert any(k[1][0] == "joinout" for k in hints)
    t, c = _run(e, _sql("q3", many))
    _same(oracle, t, _want(oracle, staged[1], "q3", many))
    assert c["fused.compact_repair"] == 1
    grown = {k: v for k, v in e._jit_cache.items() if k[0] == "nhint"}
    assert set(grown) == set(hints)     # the same keys, re-learnt
    assert any(grown[k] > hints[k] for k in hints)


# --- through coordinator + worker --------------------------------------------

@pytest.fixture(scope="module")
def served(staged):
    from igloo_tpu.cluster.client import DistributedClient
    from igloo_tpu.cluster.coordinator import CoordinatorServer
    from igloo_tpu.cluster.worker import Worker
    coord = CoordinatorServer("grpc+tcp://127.0.0.1:0", worker_timeout_s=600.0)
    worker = client = None
    try:
        addr = f"127.0.0.1:{coord.port}"
        worker = Worker(addr, port=0, heartbeat_interval_s=1.0)
        worker.start()
        deadline = time.monotonic() + 30
        while not coord.membership.live() and time.monotonic() < deadline:
            time.sleep(0.05)
        assert coord.membership.live()
        for name in TABLES:
            coord.register_table(name, ParquetTable(
                os.path.join(staged[0], f"{name}.parquet")))
        client = DistributedClient(addr)
        yield coord, worker, client
    finally:
        if client is not None:
            client.close()
        if worker is not None:
            worker.shutdown()
        coord.shutdown()


def _served_run(served, sql: str):
    coord, worker, client = served
    coord.engine.result_cache.clear()
    before = dict(tracing.counters())
    t = client.execute(sql)
    m = client.last_metrics()
    assert m.get("fragments") and not m.get("result_cache_hit")
    return t, _delta(before)


@pytest.mark.parametrize("q,sets", [("q1", Q1_SETS), ("q6", Q6_SETS),
                                    ("q3", Q3_SETS)])
def test_served_parameter_sets(served, staged, oracle, q, sets):
    cache = served[1].server._batch_cache
    for _ in range(3):
        t, c = _served_run(served, _sql(q, sets[0]))
    _same(oracle, t, _want(oracle, staged[1], q, sets[0]))
    steady, held = c, _table_entries(cache)
    for params in sets[1:]:
        t, c = _served_run(served, _sql(q, params))
        _same(oracle, t, _want(oracle, staged[1], q, params))
        # no table column is loaded again: what misses and uploads is what a
        # steady repeat of the first set does too, the fragments'
        # dependency tables (new names in every query)
        assert _table_entries(cache) == held
        assert c["cache.miss"] == steady["cache.miss"], (params, c, steady)
        assert c["xfer.h2d_bytes"] <= steady["xfer.h2d_bytes"] + 4096
        assert not c["compile_cache.miss"]
        if q == "q3" and params[0] != sets[0][0]:
            assert c["jit.miss"]
            continue
        assert not c["jit.miss"], (params, c)
        assert c["program.literal_shared"] >= 1
