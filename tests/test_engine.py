"""End-to-end engine tests (parity with the reference's engine tests,
crates/engine/src/lib.rs:146-231 + tests/integration_test.rs, re-targeted at the
TPU execution stack), plus oracle checks against pandas."""
import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from igloo_tpu.catalog import MemTable
from igloo_tpu.connectors.parquet import ParquetTable
from igloo_tpu.engine import QueryEngine
from igloo_tpu.errors import IglooError, PlanError, SqlParseError


@pytest.fixture
def engine():
    e = QueryEngine()
    e.register_table("users", pa.table({
        "id": pa.array([1, 2, 3, 4, 5], type=pa.int64()),
        "name": ["alice", "BOB", "Carol", "dave", None],
        "age": pa.array([30, 25, 35, None, 40], type=pa.int64()),
    }))
    e.register_table("orders", pa.table({
        "order_id": pa.array([100, 101, 102, 103], type=pa.int64()),
        "user_id": pa.array([1, 1, 3, 9], type=pa.int64()),
        "total": pa.array([9.5, 20.0, 3.25, 7.0]),
    }))
    return e


def test_select_42(engine):
    # parity: reference test_execute_query (lib.rs:156-184) runs SELECT 42
    t = engine.execute("SELECT 42")
    assert t.num_rows == 1
    assert t.column(0).to_pylist() == [42]


def test_capitalize_udf(engine):
    # parity: reference capitalize tests incl. NULL handling (lib.rs:186-231)
    t = engine.execute(
        "SELECT capitalize(name) AS n FROM users ORDER BY id")
    assert t.column("n").to_pylist() == ["Alice", "Bob", "Carol", "Dave", None]


def test_filter_project(engine):
    t = engine.execute("SELECT id, age * 2 AS a2 FROM users WHERE age >= 30")
    got = dict(zip(t.column("id").to_pylist(), t.column("a2").to_pylist()))
    assert got == {1: 60, 3: 70, 5: 80}


def test_join(engine):
    t = engine.execute("""
        SELECT u.name, o.total FROM users u JOIN orders o ON u.id = o.user_id
        ORDER BY o.total
    """)
    assert t.column("name").to_pylist() == ["Carol", "alice", "alice"]
    assert t.column("total").to_pylist() == [3.25, 9.5, 20.0]


def test_left_join_null_padding(engine):
    t = engine.execute("""
        SELECT u.id, o.order_id FROM users u
        LEFT JOIN orders o ON u.id = o.user_id ORDER BY u.id, o.order_id
    """)
    pairs = list(zip(t.column("id").to_pylist(), t.column("order_id").to_pylist()))
    assert pairs == [(1, 100), (1, 101), (2, None), (3, 102), (4, None), (5, None)]


def test_group_by_having(engine):
    t = engine.execute("""
        SELECT user_id, count(*) AS c, sum(total) AS s FROM orders
        GROUP BY user_id HAVING count(*) > 1
    """)
    assert t.num_rows == 1
    assert t.column("user_id").to_pylist() == [1]
    assert t.column("s").to_pylist() == [29.5]


def test_subqueries(engine):
    t = engine.execute("""
        SELECT id FROM users WHERE id IN (SELECT user_id FROM orders) ORDER BY id
    """)
    assert t.column("id").to_pylist() == [1, 3]
    t = engine.execute("""
        SELECT id FROM users WHERE id NOT IN (SELECT user_id FROM orders)
        ORDER BY id
    """)
    assert t.column("id").to_pylist() == [2, 4, 5]
    t = engine.execute("""
        SELECT id FROM users u
        WHERE EXISTS (SELECT 1 FROM orders o WHERE o.user_id = u.id)
        ORDER BY id
    """)
    assert t.column("id").to_pylist() == [1, 3]


def test_scalar_subquery(engine):
    t = engine.execute(
        "SELECT order_id FROM orders WHERE total > (SELECT avg(total) FROM orders)")
    assert t.column("order_id").to_pylist() == [101]


def test_union_distinct_intersect(engine):
    t = engine.execute("""
        SELECT user_id AS x FROM orders UNION SELECT id FROM users ORDER BY x
    """)
    assert t.column("x").to_pylist() == [1, 2, 3, 4, 5, 9]
    t = engine.execute("""
        SELECT user_id FROM orders INTERSECT SELECT id FROM users
    """)
    assert sorted(t.column(0).to_pylist()) == [1, 3]
    t = engine.execute("""
        SELECT id FROM users EXCEPT SELECT user_id FROM orders
    """)
    assert sorted(t.column(0).to_pylist()) == [2, 4, 5]


def test_case_and_strings(engine):
    t = engine.execute("""
        SELECT id, CASE WHEN age >= 35 THEN 'senior' ELSE 'junior' END AS band
        FROM users WHERE age IS NOT NULL ORDER BY id
    """)
    assert t.column("band").to_pylist() == ["junior", "junior", "senior", "senior"]
    t = engine.execute(
        "SELECT name FROM users WHERE lower(name) LIKE '%a%' ORDER BY id")
    assert t.column("name").to_pylist() == ["alice", "Carol", "dave"]


def test_distinct_and_limit(engine):
    t = engine.execute("SELECT DISTINCT user_id FROM orders ORDER BY user_id")
    assert t.column("user_id").to_pylist() == [1, 3, 9]
    t = engine.execute("SELECT id FROM users ORDER BY id LIMIT 2 OFFSET 1")
    assert t.column("id").to_pylist() == [2, 3]


def test_count_distinct(engine):
    t = engine.execute("SELECT count(DISTINCT user_id) FROM orders")
    assert t.column(0).to_pylist() == [3]


def test_utility_statements(engine):
    t = engine.execute("SHOW TABLES")
    assert set(t.column("table_name").to_pylist()) == {"users", "orders"}
    t = engine.execute("DESCRIBE users")
    assert t.column("column_name").to_pylist() == ["id", "name", "age"]
    t = engine.execute("EXPLAIN SELECT id FROM users WHERE age > 1")
    text = "\n".join(t.column("plan").to_pylist())
    assert "Scan" in text and "Filter" in text
    engine.execute("CREATE TABLE adults AS SELECT * FROM users WHERE age >= 30")
    t = engine.execute("SELECT count(*) FROM adults")
    assert t.column(0).to_pylist() == [3]
    engine.execute("DROP TABLE adults")
    with pytest.raises(IglooError):
        engine.execute("SELECT * FROM adults")


def test_errors_do_not_panic(engine):
    # reference G9: QueryEngine::execute panics on bad SQL; ours raises
    with pytest.raises(SqlParseError):
        engine.execute("SELEC broken")
    with pytest.raises(IglooError):
        engine.execute("SELECT * FROM missing_table")
    with pytest.raises(PlanError):
        engine.execute("SELECT nope FROM users")


def test_parquet_roundtrip(tmp_path, engine):
    # parity with the reference integration test: write real Parquet, register,
    # filter + sort through SQL (tests/integration_test.rs:16-75)
    rng = np.random.default_rng(3)
    t = pa.table({
        "id": pa.array(np.arange(1000), type=pa.int64()),
        "value": rng.normal(size=1000),
        "name": pa.array([f"user_{i % 37}" for i in range(1000)]),
    })
    path = tmp_path / "test.parquet"
    pq.write_table(t, path)
    engine.register_table("ptab", ParquetTable(str(path)))
    out = engine.execute(
        "SELECT id, value FROM ptab WHERE value > 1.0 ORDER BY value DESC LIMIT 5")
    df = t.to_pandas()
    want = df[df.value > 1.0].sort_values("value", ascending=False).head(5)
    assert out.column("id").to_pylist() == want["id"].tolist()


def test_query_result_metadata(engine):
    r = engine.query("SELECT count(*) FROM users")
    assert r.num_rows == 1
    assert r.elapsed_s > 0
    assert r.plan is not None


def test_cte_referenced_twice(engine):
    t = engine.execute("""
        WITH c AS (SELECT id, age FROM users WHERE age IS NOT NULL)
        SELECT x.id, y.age FROM c x JOIN c y ON x.id = y.id ORDER BY x.id
    """)
    assert t.column("id").to_pylist() == [1, 2, 3, 5]


def test_global_aggregate_having_false(engine):
    t = engine.execute("SELECT count(*) FROM users HAVING 1 = 0")
    assert t.num_rows == 0


def test_negative_integer_division_consistency(engine):
    # folded constant and runtime kernel must agree: SQL truncates toward zero
    t = engine.execute("SELECT -7 / 2 AS q, -7 % 2 AS r")
    assert t.column("q").to_pylist() == [-3]
    assert t.column("r").to_pylist() == [-1]
    t = engine.execute("SELECT id FROM users WHERE id - 5 = -7 / 2 + 1")
    assert t.column("id").to_pylist() == [3]


def test_right_join_using_coalesces_key(engine):
    e = QueryEngine()
    e.register_table("l", pa.table({"a": pa.array([1], type=pa.int64()),
                                    "lv": pa.array([10], type=pa.int64())}))
    e.register_table("r", pa.table({"a": pa.array([1, 99], type=pa.int64()),
                                    "rv": pa.array([100, 990], type=pa.int64())}))
    t = e.execute("SELECT a, lv, rv FROM l RIGHT JOIN r USING (a) ORDER BY a")
    assert t.column("a").to_pylist() == [1, 99]  # 99 from right side, not NULL
    assert t.column("lv").to_pylist() == [10, None]


def test_natural_left_join_no_common_cols(engine):
    e = QueryEngine()
    e.register_table("l", pa.table({"a": pa.array([1, 2], type=pa.int64())}))
    e.register_table("r", pa.table({"b": pa.array([], type=pa.int64())}))
    t = e.execute("SELECT * FROM l NATURAL LEFT JOIN r ORDER BY a")
    # outer semantics preserved: every left row survives null-extended
    assert t.column("a").to_pylist() == [1, 2]
    assert t.column("b").to_pylist() == [None, None]


def test_deep_correlation_rejected_cleanly(engine):
    from igloo_tpu.errors import NotSupportedError
    with pytest.raises((NotSupportedError, PlanError)):
        engine.execute("""
            SELECT id FROM users u WHERE EXISTS (
                SELECT 1 FROM orders o WHERE EXISTS (
                    SELECT 1 FROM orders o2 WHERE o2.user_id = u.id))
        """)


def test_random_query_vs_pandas(engine):
    rng = np.random.default_rng(11)
    n = 2000
    t = pa.table({
        "g": pa.array(rng.integers(0, 23, n), type=pa.int64()),
        "x": rng.normal(size=n),
        "y": pa.array(rng.integers(-50, 50, n), type=pa.int64()),
    })
    engine.register_table("r", t)
    out = engine.execute("""
        SELECT g, count(*) AS c, sum(x) AS sx, min(y) AS mn, max(y) AS mx
        FROM r WHERE y % 2 = 0 GROUP BY g ORDER BY g
    """)
    df = t.to_pandas()
    df = df[df.y % 2 == 0]
    want = df.groupby("g").agg(c=("x", "size"), sx=("x", "sum"),
                               mn=("y", "min"), mx=("y", "max")).reset_index()
    assert out.column("g").to_pylist() == want["g"].tolist()
    assert out.column("c").to_pylist() == want["c"].tolist()
    np.testing.assert_allclose(out.column("sx").to_pylist(), want["sx"], rtol=1e-9)
    assert out.column("mn").to_pylist() == want["mn"].tolist()
    assert out.column("mx").to_pylist() == want["mx"].tolist()


@pytest.fixture
def corners():
    e = QueryEngine()
    e.register_table("t", pa.table({
        "a": pa.array([1, 2, None, 4, 5], type=pa.int64()),
        "b": pa.array([1.5, None, 2.5, 2.5, 0.0]),
        "s": pa.array(["x", "y", None, "x", "z"]),
    }))
    e.register_table("u", pa.table({
        "k": pa.array([1, 2, 2, 6], type=pa.int64()),
        "v": pa.array(["p", "q", "r", "s"]),
    }))
    return e


def assert_rows(got: pa.Table, names: list, rows: list, ordered: bool,
                label: str = "") -> None:
    """`got` holds exactly `rows` under `names`: in order where `ordered`,
    else as a multiset; floats to rel=1e-9."""
    assert got.column_names == names, label
    grows = list(zip(*got.to_pydict().values()))
    wrows = list(rows)
    if not ordered:
        grows, wrows = sorted(grows, key=repr), sorted(wrows, key=repr)
    assert len(grows) == len(wrows), f"{label}: {grows} != {wrows}"
    for i, (g, w) in enumerate(zip(grows, wrows)):
        for gv, wv, name in zip(g, w, names):
            if isinstance(wv, float) and gv is not None:
                assert gv == pytest.approx(wv, rel=1e-9), \
                    f"{label} row {i} col {name}: {gv} != {wv}"
            else:
                assert gv == wv, f"{label} row {i} col {name}: {gv} != {wv}"


# t: (a, b, s) = (1, 1.5, x) (2, NULL, y) (NULL, 2.5, NULL) (4, 2.5, x)
# (5, 0.0, z); u: (k, v) = (1, p) (2, q) (2, r) (6, s). Each answer is SQL's,
# worked by hand: a NULL predicate keeps no row (three-valued logic), NULL
# keys join nothing and group together, an outer join pads with NULLs, a
# DESC key puts NULLs first and an ASC key last; x / 0 is NULL here (as in
# MySQL and SQLite), not an error.
SEMANTICS_CORNERS = [
    ("SELECT a, b FROM t WHERE a > 1 AND b > 1.0",
     ["a", "b"], [(4, 2.5)], False),
    ("SELECT a FROM t WHERE NOT (b > 2.0)",                    # 3VL NOT
     ["a"], [(1,), (5,)], False),
    ("SELECT a FROM t WHERE b > 2.0 OR a > 3",                 # Kleene OR
     ["a"], [(None,), (4,), (5,)], False),
    ("SELECT a FROM t WHERE s IS NOT NULL",
     ["a"], [(1,), (2,), (4,), (5,)], False),
    ("SELECT a / 0 AS z, a % 2 AS m FROM t",                   # div by 0
     ["z", "m"], [(None, 1), (None, 0), (None, None), (None, 0), (None, 1)],
     False),
    ("SELECT s, count(*) AS n, sum(a) AS sa FROM t GROUP BY s",
     ["s", "n", "sa"],
     [("x", 2, 5), ("y", 1, 2), (None, 1, None), ("z", 1, 5)], False),
    ("SELECT count(DISTINCT s) AS d FROM t", ["d"], [(3,)], False),
    ("SELECT min(b) AS mn, max(b) AS mx, avg(a) AS av FROM t",
     ["mn", "mx", "av"], [(0.0, 2.5, 3.0)], False),
    ("SELECT DISTINCT s FROM t",
     ["s"], [("x",), ("y",), (None,), ("z",)], False),
    ("SELECT a, s FROM t ORDER BY s DESC, a ASC",
     ["a", "s"], [(None, None), (5, "z"), (2, "y"), (1, "x"), (4, "x")],
     True),
    ("SELECT a FROM t ORDER BY b NULLS FIRST, a NULLS FIRST",
     ["a"], [(2,), (5,), (1,), (None,), (4,)], True),
    ("SELECT t.a, u.v FROM t JOIN u ON t.a = u.k",
     ["a", "v"], [(1, "p"), (2, "q"), (2, "r")], False),
    ("SELECT t.a, u.v FROM t LEFT JOIN u ON t.a = u.k",
     ["a", "v"], [(1, "p"), (2, "q"), (2, "r"), (None, None), (4, None),
                  (5, None)], False),
    ("SELECT u.k, t.a FROM t RIGHT JOIN u ON t.a = u.k",
     ["k", "a"], [(1, 1), (2, 2), (2, 2), (6, None)], False),
    ("SELECT t.a, u.v FROM t FULL JOIN u ON t.a = u.k",
     ["a", "v"], [(1, "p"), (2, "q"), (2, "r"), (None, None), (4, None),
                  (5, None), (None, "s")], False),
    ("SELECT upper(s) AS us, length(s) AS ls FROM t",
     ["us", "ls"], [("X", 1), ("Y", 1), (None, None), ("X", 1), ("Z", 1)],
     False),
    ("SELECT substr(s, 1, 1) AS c1 FROM t",
     ["c1"], [("x",), ("y",), (None,), ("x",), ("z",)], False),
    ("SELECT a FROM t WHERE s LIKE 'x%'", ["a"], [(1,), (4,)], False),
    ("SELECT a FROM t WHERE s IN ('x', 'z')",
     ["a"], [(1,), (4,), (5,)], False),
    ("SELECT a FROM t WHERE a IN (1, 4)", ["a"], [(1,), (4,)], False),
    ("SELECT CASE WHEN a > 2 THEN a ELSE 0 END AS c FROM t",
     ["c"], [(0,), (0,), (0,), (4,), (5,)], False),
    ("SELECT a FROM t WHERE a > (SELECT min(k) FROM u)",
     ["a"], [(2,), (4,), (5,)], False),
    ("SELECT capitalize(v) AS cv FROM u",
     ["cv"], [("P",), ("Q",), ("R",), ("S",)], False),
    ("SELECT a, b FROM t ORDER BY a LIMIT 2 OFFSET 1",
     ["a", "b"], [(2, None), (4, 2.5)], True),
    ("SELECT count(*) AS n FROM t WHERE a IS NULL", ["n"], [(1,)], False),
]


@pytest.mark.parametrize("route", ["fused", "staged"])
@pytest.mark.parametrize("sql,names,rows,ordered", SEMANTICS_CORNERS,
                         ids=[c[0] for c in SEMANTICS_CORNERS])
def test_semantics_corners(corners, monkeypatch, route, sql, names, rows,
                           ordered):
    """SQL's corners on both single-chip device routes: the whole-plan
    fused program and the staged executor."""
    from igloo_tpu.exec.executor import Executor
    if route == "staged":
        monkeypatch.setattr(Executor, "_FUSE", False)
    res = corners.query(sql)
    assert res.stats.tier == "device"
    assert_rows(res.table, names, rows, ordered, label=sql)


def test_small_parquet_runs_on_the_device(tmp_path):
    """A 100-row Parquet file — the smallest source there is — runs on the
    device tier."""
    p = tmp_path / "small.parquet"
    pq.write_table(pa.table({"x": list(range(100))}), p)
    eng = QueryEngine()
    eng.register_table("small", ParquetTable(str(p)))
    res = eng.query("SELECT sum(x) AS s FROM small WHERE x > 10")
    assert res.table.column("s").to_pylist() == [sum(range(11, 100))]
    assert res.stats.tier == "device"
