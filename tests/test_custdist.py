"""TPC-H Q13, "Customer Distribution", as the benchmark's in-process SF10
cell runs it (`benchmark/queries/q13.sql`, deployment `embedded_fused`):
`customer LEFT JOIN orders` under a NOT LIKE in the ON clause, a count per
customer, and a second GROUP BY on that count. Over the spec's sparse order
keys and its comment text (`benchmark/datagen_spec_text.py`: every order its
own comment) the fused compiler accepts the plan and it runs as ONE program
that equals the benchmark's pandas reference (`benchmark/oracle/
tpch_pandas.py q13`); the customers without orders (every custkey that is a
multiple of 3, clause 4.2) are in its `c_count = 0` row; an INNER join's
answer is refused by the same comparison. Each plan walk counts the
positional join (`join.direct_routes`), the count per customer's scatter
(`agg.direct_scatter`), the second GROUP BY's packed lane (`pack.agg`: the
count carries the bound its input's capacity gives it) and the ORDER BY's
(`pack.sort`) once, and matches the LIKE pattern against the comment's
dictionary only on the first. q1, q3 and q6 keep their own decisions."""
import os

import numpy as np
import pytest

from igloo_tpu.engine import QueryEngine
from igloo_tpu.exec.fused import FusedCompiler
from igloo_tpu.utils import tracing
from test_direct_table_budget import BENCH, bench_module

TABLES = ("customer", "orders", "lineitem")
COUNTERS = ("join.direct_routes", "agg.direct_scatter", "pack.agg",
            "pack.sort")


def query_text(name: str) -> str:
    with open(os.path.join(BENCH, "queries", f"{name}.sql")) as f:
        return f.read()


@pytest.fixture(scope="module")
def staged():
    """sf -> the tables q1, q3 and q13 read at `sf`, order keys, customer
    keys and comments as the spec draws them; one copy per scale for the
    module."""
    gen, tables = bench_module("datagen_spec_text"), {}

    def at(sf: float) -> dict:
        if sf not in tables:
            tables[sf] = gen.gen_tables(sf=sf, seed=4200000105,
                                        tables=list(TABLES))
        return tables[sf]
    return at


def engine(tables: dict) -> QueryEngine:
    eng = QueryEngine(cache_budget_bytes=1 << 30, chunk_budget_bytes=2 << 30)
    for name, tbl in tables.items():
        eng.register_table(name, tbl)
    return eng


def verdict(eng: QueryEngine, sql: str) -> None:
    """What `embedded_fused` asks before a text's first execution: raises
    FusionUnsupported where the plan would fall to the staged executor."""
    FusedCompiler(eng._executor()).compile(eng.plan(sql))


def reference(tables: dict):
    compare = bench_module("compare")
    oracle = bench_module("oracle/tpch_pandas")
    return oracle.q13({n: compare.frame(t) for n, t in tables.items()}), \
        compare


@pytest.mark.parametrize("sf", [0.05, 0.5])
def test_q13_is_one_program_and_equals_the_reference(staged, sf):
    """SF 0.05: 7,500 customers, a small-segment scatter; SF 0.5: 75,000,
    the big-segment branch the SF10 cell takes."""
    tables = staged(sf)
    want, compare = reference(tables)
    eng = engine(tables)
    sql = query_text("q13")
    verdict(eng, sql)
    with tracing.counter_delta() as d:
        res = eng.query(sql)
    assert res.stats.tier == "device"
    assert d.get("fused.execute") == 1
    assert "fused.unsupported" not in d and "fused.nofuse_sentinel" not in d
    assert d.get("join.direct_routes") == 1
    err, wrong, why = compare.compare(res.table, want)
    assert wrong == 0, why
    assert err == 0.0                  # integers only
    got = compare.frame(res.table)
    assert list(got.columns) == ["c_count", "custdist"]
    assert got.custdist.sum() == tables["customer"].num_rows


def test_customers_without_orders_are_in_the_zero_row(staged):
    tables = staged(0.05)
    res = engine(tables).query(query_text("q13"))
    rows = dict(zip(res.table.column("c_count").to_pylist(),
                    res.table.column("custdist").to_pylist()))
    keys = tables["customer"].column("c_custkey").to_numpy()
    orderless = int(np.sum(keys % 3 == 0))
    assert orderless > 0
    assert rows[0] >= orderless


def test_an_inner_join_answer_is_refused(staged):
    """The control: the same text with an INNER join loses the customers
    without orders; the comparison that decides `correct` must see it."""
    tables = staged(0.05)
    want, compare = reference(tables)
    sql = query_text("q13")
    assert sql.count("LEFT JOIN") == 1
    inner = engine(tables).query(sql.replace("LEFT JOIN", "JOIN"))
    assert 0 not in inner.table.column("c_count").to_pylist()
    _, wrong, _ = compare.compare(inner.table, want)
    assert wrong > 0


@pytest.mark.parametrize("name,sf,counts", [
    ("q13", 0.5, {"join.direct_routes": 1, "agg.direct_scatter": 1,
                  "pack.agg": 1, "pack.sort": 1}),
    ("q1", 0.05, {"agg.direct_scatter": 1, "pack.sort": 1}),
    ("q3", 0.05, {"join.direct_routes": 2, "pack.agg": 1}),
    ("q13", 0.05, {"join.direct_routes": 1, "agg.direct_scatter": 1,
                   "pack.agg": 1, "pack.sort": 1}),
    ("q6", 0.05, {}),
])
def test_counters_once_per_plan_walk(staged, name, sf, counts):
    eng = engine(staged(sf))
    sql = query_text(name)
    eng.query(sql)                     # loads the scans
    for _ in range(2):
        with tracing.counter_delta() as d:
            FusedCompiler(eng._executor()).compile(eng.plan(sql))
        assert {k: d.get(k) for k in COUNTERS if d.get(k)} == counts
    eng.result_cache.clear()
    with tracing.counter_delta() as d:
        eng.query(sql)
    assert d.get("fused.execute") == 1
    assert {k: d.get(k) for k in COUNTERS if d.get(k)} == counts


def test_the_like_is_matched_once_per_dictionary(staged, monkeypatch):
    """The comment's dictionary holds an entry an order: the NOT LIKE's
    verdicts are matched on the first plan walk and the same table (the
    same padded, uploaded array) serves every later one."""
    import pyarrow.compute as pc
    tables = staged(0.05)
    n_orders = tables["orders"].num_rows
    eng = engine(tables)
    sql = query_text("q13")
    calls = []
    real = pc.match_substring_regex
    monkeypatch.setattr(pc, "match_substring_regex",
                        lambda values, pattern: calls.append(len(values))
                        or real(values, pattern))
    first = eng.query(sql)
    assert len(calls) == 1 and calls[0] > 0.9 * n_orders
    pools = []
    for _ in range(2):
        eng.result_cache.clear()
        assert eng.query(sql).table.equals(first.table)
        fc = FusedCompiler(eng._executor())
        fc.compile(eng.plan(sql))
        pools.append([a for a in fc.pool.arrays
                      if getattr(a, "dtype", None) == np.bool_])
    assert len(calls) == 1
    [lut], [again] = pools
    assert lut is again and len(lut) >= n_orders * 0.9
