"""A direct-scatter aggregate wider than `K.SMALL_NSEG` segments leaves its
groups where their segment ids put them (`exec/aggregate.py
groups_in_place`): no compaction sort, no gather per column; `live` is the
group mask. The verdict is a function of the segment space alone, which
every program key holds already. Every consumer reads `live`, so answers
equal the pandas reference in both executors whatever node reads the
groups; the counter `agg.groups_in_place` counts each such aggregate once a
plan walk, and TPC-H q1's 16-segment aggregate, q3's packed GROUP BY and
q6's global one keep their programs."""
import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

from igloo_tpu.exec import kernels as K
from igloo_tpu.exec.aggregate import _segment_space, groups_in_place
from igloo_tpu.exec.executor import Executor
from igloo_tpu.exec.fused import FusedCompiler
from igloo_tpu.utils import tracing
from test_count_bounds import engine, run
from test_custdist import engine as spec_engine
from test_custdist import query_text, reference
from test_direct_table_budget import bench_module

COUNTER = "agg.groups_in_place"


@pytest.mark.parametrize("seg_dims,verdict", [
    (((1001, 0),), True),               # k in 0..999, NULL: 1,024 segments
    (((10, 0), (10, 0)), True),         # two keys: 128 segments
    (((63, 0),), False),                # exactly SMALL_NSEG segments
    (((4, 0), (3, 0)), False),          # TPC-H q1's 16
    (None, False),                      # the sort path: no segment ids
], ids=["wide", "two_keys_wide", "at_small_nseg", "q1_16_segments",
        "sort_path"])
def test_the_verdict_by_segment_space(seg_dims, verdict):
    if seg_dims is not None:
        assert (_segment_space(seg_dims)[1] > K.SMALL_NSEG) is verdict
    with tracing.counter_delta() as d:
        assert groups_in_place(seg_dims) is verdict
    assert COUNTER not in d            # the executors count, once a walk


# --- answers, both executors ------------------------------------------------

@pytest.fixture(scope="module")
def spec_tables():
    return bench_module("datagen_spec_text").gen_tables(
        sf=0.05, seed=4400000105, tables=["customer", "orders"])


@pytest.mark.parametrize("executor", ["fused", "staged"])
def test_q13_equals_the_reference(spec_tables, executor):
    want, compare = reference(spec_tables)
    got, d = run(spec_tables, query_text("q13"), executor)
    assert d.get(COUNTER) == 1
    assert d.get("agg.direct_scatter") == 1
    err, wrong, why = compare.compare(got, want)
    assert wrong == 0, why
    assert err == 0.0


def dense_table(n: int = 20000, seed: int = 45) -> pa.Table:
    """k in 0..999 with a NULL key on a fifth of the rows (the largest
    group), v an integer with NULLs, w a float."""
    rng = np.random.default_rng(seed)
    k = rng.integers(0, 1000, n)
    v = rng.integers(-50, 100, n)
    return pa.table({
        "k": pa.array(k, mask=rng.random(n) < 0.2),
        "v": pa.array(v, mask=rng.random(n) < 0.1),
        "w": pa.array(rng.random(n) * 100.0)})


def _per_key(df: pd.DataFrame) -> pd.DataFrame:
    g = df.groupby("k", dropna=False)
    return pd.DataFrame({"n": g.size(), "nv": g["v"].count(),
                         "s": g["v"].sum(min_count=1),
                         "sw": g["w"].sum()}).reset_index()


# case -> (text, the pandas answer from the groups per k, aggregates left
# in place: the second GROUP BY's count is bounded by its input's 32,768
# lanes, so it scatters too and leaves its groups to the ORDER BY)
DENSE = {
    "second_group_by": (
        """SELECT n, COUNT(*) AS c, SUM(s) AS ss FROM
             (SELECT k, COUNT(*) AS n, SUM(v) AS s FROM t GROUP BY k) AS g
           GROUP BY n ORDER BY n""",
        lambda p: p.groupby("n").agg(c=("k", "size"), ss=("s", "sum"))
        .reset_index().sort_values("n").reset_index(drop=True), 2),
    "sort_path_group_by": (
        """SELECT s2, COUNT(*) AS c FROM
             (SELECT k, COALESCE(SUM(v), -1000) AS s2 FROM t GROUP BY k) AS g
           GROUP BY s2 ORDER BY s2""",
        lambda p: p.assign(s2=p.s.fillna(-1000).astype(np.int64))
        .groupby("s2").size().rename("c").reset_index()
        .sort_values("s2").reset_index(drop=True), 1),
    "order_by_limit": (
        """SELECT k, n, nv FROM
             (SELECT k, COUNT(*) AS n, COUNT(v) AS nv FROM t GROUP BY k) AS g
           ORDER BY n DESC, k LIMIT 12""",
        lambda p: p.sort_values(["n", "k"], ascending=[False, True])
        .head(12)[["k", "n", "nv"]].reset_index(drop=True), 1),
    "global_count_sum": (
        """SELECT COUNT(*) AS groups, SUM(n) AS n, SUM(nv) AS nv,
                  SUM(sw) AS sw FROM
             (SELECT k, COUNT(*) AS n, COUNT(v) AS nv, SUM(w) AS sw
              FROM t GROUP BY k) AS g""",
        lambda p: pd.DataFrame({"groups": [len(p)], "n": [p.n.sum()],
                                "nv": [p.nv.sum()], "sw": [p.sw.sum()]}),
        1),
}


@pytest.mark.parametrize("executor", ["fused", "staged"])
@pytest.mark.parametrize("case", list(DENSE))
def test_dense_group_by_with_nulls_equals_pandas(executor, case):
    tbl = dense_table()
    sql, expect, in_place = DENSE[case]
    got, d = run({"t": tbl}, sql, executor)
    got = got.to_pandas()
    assert d.get(COUNTER) == in_place, case
    want = expect(_per_key(tbl.to_pandas()))
    assert list(got.columns) == list(want.columns)
    assert len(got) == len(want)
    for col in want.columns:
        g, w = got[col].to_numpy(), want[col].to_numpy()
        if col == "k":                  # the NULL group leads: most rows
            assert pd.isna(g[0]) and pd.isna(w[0])
            g, w = g[1:], w[1:]
        np.testing.assert_allclose(g.astype(float), w.astype(float),
                                   rtol=1e-12, err_msg=f"{case}.{col}")


U = pa.table({"k": pa.array(np.arange(0, 1000, 7)),
              "x": pa.array(np.arange(0, 1000, 7))})
G = "(SELECT k, COUNT(*) AS n, SUM(v) AS s FROM t GROUP BY k) AS g"


def _u() -> pd.DataFrame:
    return U.to_pandas()


# consumer -> (text, the pandas answer from the groups per k, rows in any
# order, aggregates left in place, answered by one fused program). Each
# reads the 1,024-segment count per k where it lies.
CONSUMERS = {
    "root": ("SELECT k, COUNT(*) AS n FROM t GROUP BY k",
             lambda p: p[["k", "n"]], 1, True),
    "having": ("SELECT k, COUNT(*) AS n FROM t GROUP BY k "
               "HAVING COUNT(*) > 20",
               lambda p: p[p.n > 20][["k", "n"]], 1, True),
    "filter": (f"SELECT k, n FROM {G} WHERE s > 100",
               lambda p: p[p.s > 100][["k", "n"]], 1, True),
    "join": (f"SELECT g.k, g.n, u.x FROM {G} JOIN u ON g.k = u.k",
             lambda p: p.merge(_u(), on="k")[["k", "n", "x"]], 1, True),
    "window": (f"SELECT k, n, RANK() OVER (ORDER BY n DESC) AS r FROM {G}",
               lambda p: p.assign(r=p.n.rank(method="min", ascending=False)
                                  .astype(np.int64))[["k", "n", "r"]],
               1, True),
    "distinct": (f"SELECT DISTINCT n FROM {G}",
                 lambda p: p[["n"]].drop_duplicates(), 1, True),
    "count_distinct": (f"SELECT COUNT(DISTINCT n) AS c FROM {G}",
                       lambda p: pd.DataFrame({"c": [p.n.nunique()]}), 2,
                       True),
    "union_all": (f"SELECT n FROM {G} UNION ALL SELECT x AS n FROM u",
                  lambda p: pd.concat([p[["n"]], _u()[["x"]].rename(
                      columns={"x": "n"})]), 1, False),
    "scalar_subquery": (
        f"""SELECT k, n FROM {G} WHERE n > (SELECT AVG(n) FROM
              (SELECT k, COUNT(*) AS n FROM t GROUP BY k) AS h)""",
        lambda p: p[p.n > p.n.mean()][["k", "n"]], 2, True),
    "in_subquery": (
        """SELECT x FROM u WHERE x IN (SELECT n FROM
             (SELECT k, COUNT(*) AS n FROM t GROUP BY k) AS h)""",
        lambda p: _u()[_u().x.isin(p.n)][["x"]], 1, True),
}


def _rows(df: pd.DataFrame) -> np.ndarray:
    """The rows as floats in one order (NULL as -inf)."""
    a = df.astype(float).fillna(-np.inf).to_numpy()
    return a[np.lexsort(a.T[::-1])] if len(a) else a


@pytest.mark.parametrize("executor", ["fused", "staged"])
@pytest.mark.parametrize("case", list(CONSUMERS))
def test_every_consumer_reads_groups_in_place(executor, case):
    sql, expect, in_place, fuses = CONSUMERS[case]
    tbl = dense_table()
    eng = engine({"t": tbl, "u": U})
    with tracing.counter_delta() as d:
        if executor == "fused":
            got = eng.execute(sql)
        else:
            ex = Executor(eng._jit_cache, batch_cache=eng.batch_cache)
            got = ex._staged_to_arrow(eng.plan(sql))
    if executor == "fused":
        assert bool(d.get("fused.execute")) is fuses, case
        assert bool(d.get("fused.unsupported")) is not fuses, case
    assert d.get(COUNTER) == in_place, case
    want = expect(_per_key(tbl.to_pandas()))
    got = got.to_pandas()
    assert list(got.columns) == list(want.columns)
    assert len(got) == len(want)
    np.testing.assert_allclose(_rows(got), _rows(want), rtol=1e-12,
                               err_msg=case)


@pytest.mark.parametrize("executor", ["fused", "staged"])
def test_a_limit_alone_takes_live_groups(executor):
    """LIMIT without ORDER BY: any five groups, each a real one."""
    tbl = dense_table()
    got, d = run({"t": tbl}, "SELECT k, COUNT(*) AS n FROM t GROUP BY k "
                             "LIMIT 5", executor)
    assert d.get(COUNTER) == 1
    want = _rows(_per_key(tbl.to_pandas())[["k", "n"]])
    got = _rows(got.to_pandas())
    assert len(got) == 5
    for row in got:
        assert (want == row).all(axis=1).any(), row


# --- the counter once a plan walk; q1, q3, q6 keep their programs ----------

@pytest.fixture(scope="module")
def tpch_tables():
    return bench_module("datagen_spec_text").gen_tables(
        sf=0.05, seed=4400000106,
        tables=["customer", "orders", "lineitem"])


def _fused_key(eng, sql: str):
    eng.query(sql)                      # loads the scans
    with tracing.counter_delta() as d:
        _run, key, _meta = FusedCompiler(eng._executor()).compile(
            eng.plan(sql))
    return key, d


@pytest.mark.parametrize("name,count", [("q13", 1), ("q1", 0), ("q3", 0),
                                        ("q6", 0)])
def test_the_counter_and_the_key_per_plan_walk(tpch_tables, name, count):
    eng = spec_engine(tpch_tables)
    sql = query_text(name)
    key, d = _fused_key(eng, sql)
    assert d.get(COUNTER, 0) == count
    aggs = [fp for fp in key[1]
            if isinstance(fp, tuple) and fp and fp[0] == "agg"]
    # ("agg", shape, funcs, schema, seg_dims, pack_spec), the parent's form:
    # the verdict is read off the key's seg_dims, no flag of its own
    assert all(fp[6:] in ((), ("pair_sums",)) for fp in aggs)
    assert sum(groups_in_place(fp[4]) for fp in aggs) == count
    eng.result_cache.clear()
    with tracing.counter_delta() as d:
        eng.query(sql)
    assert d.get("fused.execute") == 1
    assert d.get(COUNTER, 0) == count
