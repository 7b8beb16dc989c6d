"""Sharded execution tier tests on the virtual 8-device CPU mesh.

The reference has NO distributed tests (SURVEY.md §4); the strategy here is
the one SURVEY invents: every sharded plan must produce exactly the rows the
single-device executor produces. Shuffle correctness (all_to_all bucket
framing, overflow re-runs) is exercised through skewed keys.
"""
import os

import numpy as np
import pyarrow as pa
import pytest

from igloo_tpu.engine import QueryEngine
from igloo_tpu.parallel.executor import ShardedExecutor
from igloo_tpu.parallel.mesh import make_mesh


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(8)


@pytest.fixture(scope="module")
def engine():
    rng = np.random.default_rng(7)
    n = 3000
    t = pa.table({
        "k": rng.integers(0, 40, n),
        "v": rng.random(n),
        "q": rng.integers(1, 50, n).astype(np.int64),
        "s": pa.array([f"cat{i % 7}" for i in range(n)]),
        "flag": pa.array([bool(i % 3) for i in range(n)]),
    })
    d = pa.table({
        "k": np.arange(40),
        "name": pa.array([f"n{i:02d}" for i in range(40)]),
        "grp": pa.array([f"g{i % 5}" for i in range(40)]),
    })
    skew = pa.table({
        "k": np.where(rng.random(n) < 0.9, 3, rng.integers(0, 40, n)),
        "v": rng.random(n),
    })
    nulls = pa.table({
        "k": pa.array([None if i % 5 == 0 else i % 11 for i in range(400)],
                      type=pa.int64()),
        "v": pa.array([None if i % 7 == 0 else float(i) for i in range(400)]),
    })
    eng = QueryEngine()
    eng.register_table("t", t)
    eng.register_table("d", d)
    eng.register_table("skew", skew)
    eng.register_table("nl", nulls)
    return eng


def check(engine, mesh, sql, **kw):
    plan = engine.plan(sql)
    got = ShardedExecutor(mesh=mesh).execute_to_arrow(plan).to_pandas()
    want = engine.execute(sql).to_pandas()
    import pandas as pd
    pd.testing.assert_frame_equal(got.reset_index(drop=True),
                                  want.reset_index(drop=True),
                                  check_dtype=False, atol=1e-9, **kw)


# --- aggregates ---

def test_sharded_groupby_all_partials(engine, mesh):
    check(engine, mesh,
          "SELECT s, SUM(v) AS sv, COUNT(*) AS c, COUNT(v) AS cv, "
          "AVG(v) AS av, MIN(v) AS mn, MAX(q) AS mx "
          "FROM t GROUP BY s ORDER BY s")


def test_sharded_global_agg(engine, mesh):
    check(engine, mesh, "SELECT SUM(v) AS sv, COUNT(*) AS c, AVG(q) AS aq, "
          "MIN(v) AS mn, MAX(v) AS mx FROM t")


def test_sharded_agg_with_filter_project(engine, mesh):
    check(engine, mesh,
          "SELECT k, SUM(v * q) AS wv FROM t WHERE flag AND v > 0.25 "
          "GROUP BY k ORDER BY k")


def test_sharded_groupby_string_minmax(engine, mesh):
    # MIN/MAX over a dictionary-encoded string column keeps the dictionary
    check(engine, mesh,
          "SELECT k % 4 AS b, MIN(s) AS mn, MAX(s) AS mx FROM t "
          "GROUP BY k % 4 ORDER BY b")


def test_sharded_agg_nulls(engine, mesh):
    check(engine, mesh,
          "SELECT k, COUNT(*) AS c, COUNT(v) AS cv, SUM(v) AS sv, "
          "AVG(v) AS av FROM nl GROUP BY k ORDER BY k NULLS FIRST")


def test_sharded_agg_arguments_that_print_alike(engine, mesh):
    """LIKE, NOT LIKE and ILIKE arguments of one small-domain GROUP BY keep
    lanes of their own in the per-shard partial aggregate (they printed
    alike before Like's repr named every field; what shares a lane is
    decided once, in `_direct_aggregate`, by `E.fingerprint`)."""
    sql = ("SELECT grp, SUM(CASE WHEN name LIKE 'n0%' THEN 1 ELSE 0 END) AS a, "
           "SUM(CASE WHEN name NOT LIKE 'n0%' THEN 1 ELSE 0 END) AS b, "
           "SUM(CASE WHEN name ILIKE 'N0%' THEN 1 ELSE 0 END) AS c, "
           "SUM(CASE WHEN name LIKE 'N0%' THEN 1 ELSE 0 END) AS d "
           "FROM d GROUP BY grp ORDER BY grp")
    got = ShardedExecutor(mesh=mesh).execute_to_arrow(engine.plan(sql))
    # names n00..n39, grp = i % 5: two of each group's eight start with n0
    assert got.to_pydict() == {"grp": [f"g{i}" for i in range(5)],
                               "a": [2] * 5, "b": [6] * 5, "c": [2] * 5,
                               "d": [0] * 5}
    check(engine, mesh, sql)


def test_sharded_agg_skewed_groups_overflow_rerun(engine, mesh):
    # 90% of rows share one key: per-device buckets overflow, the deferred
    # overflow flag fires, and the executor re-runs in exact mode
    check(engine, mesh,
          "SELECT k, SUM(v) AS sv, COUNT(*) AS c FROM skew "
          "GROUP BY k ORDER BY k")


def test_sharded_count_distinct(engine, mesh):
    # distinct aggregates take the gathered single-device fallback path
    check(engine, mesh,
          "SELECT s, COUNT(DISTINCT k) AS dk FROM t GROUP BY s ORDER BY s")


# --- joins ---

def test_sharded_inner_join_agg(engine, mesh):
    check(engine, mesh,
          "SELECT d.grp, SUM(t.v) AS sv, COUNT(*) AS c FROM t "
          "JOIN d ON t.k = d.k GROUP BY d.grp ORDER BY d.grp")


def test_sharded_left_join(engine, mesh):
    check(engine, mesh,
          "SELECT t.k, t.v, d.name FROM t LEFT JOIN d ON t.k = d.k "
          "WHERE t.k < 5 ORDER BY t.k, t.v")


def test_sharded_semi_anti_join(engine, mesh):
    check(engine, mesh,
          "SELECT k, v FROM t WHERE k IN (SELECT k FROM d WHERE k < 10) "
          "ORDER BY k, v")
    check(engine, mesh,
          "SELECT COUNT(*) AS c FROM t WHERE k NOT IN "
          "(SELECT k FROM d WHERE k < 10)")


def test_sharded_join_skew_overflow_rerun(engine, mesh):
    check(engine, mesh,
          "SELECT d.name, COUNT(*) AS c FROM skew JOIN d ON skew.k = d.k "
          "GROUP BY d.name ORDER BY c DESC, d.name")


def test_sharded_join_residual(engine, mesh):
    check(engine, mesh,
          "SELECT t.k, SUM(t.v) AS sv FROM t JOIN d ON t.k = d.k "
          "AND t.v > 0.5 GROUP BY t.k ORDER BY t.k")


def test_sharded_join_null_keys(engine, mesh):
    check(engine, mesh,
          "SELECT a.k, COUNT(*) AS c FROM nl a JOIN nl b ON a.k = b.k "
          "GROUP BY a.k ORDER BY a.k")


# --- other operators over sharded inputs ---

def test_sharded_sort_limit(engine, mesh):
    check(engine, mesh,
          "SELECT k, v FROM t ORDER BY v DESC LIMIT 17")


def test_sharded_distinct(engine, mesh):
    check(engine, mesh, "SELECT DISTINCT s, k % 3 AS m FROM t ORDER BY s, m")


def test_sharded_union(engine, mesh):
    check(engine, mesh,
          "SELECT k, v FROM t WHERE k < 3 UNION ALL "
          "SELECT k, v FROM skew WHERE k > 35 ORDER BY k, v")


def test_sharded_nested_setops(engine):
    # nested set ops exercise the exec-override restore path (a deleted
    # override used to drop the outer frame's gather and then AttributeError).
    # Two devices: the path does not depend on the mesh's width, and its
    # programs take 32 s to compile for eight here, 14 s for two
    check(engine, make_mesh(2),
          "SELECT s FROM t WHERE k < 10 INTERSECT SELECT s FROM t "
          "EXCEPT SELECT grp FROM d ORDER BY s")


def test_sharded_setops_no_replication(mesh, monkeypatch):
    """INTERSECT/EXCEPT and UNION ALL on well-spread inputs must execute
    fully sharded: replicate() (the gather-to-every-device fallback) must
    NOT run, and no intermediate may materialize a replicated full copy
    (round-4 verdict weak #6)."""
    import igloo_tpu.parallel.executor as PE
    rng = np.random.default_rng(3)
    n = 4096
    a = pa.table({"x": rng.integers(0, 5000, n),
                  "s": pa.array([f"v{i % 257}" for i in range(n)])})
    b = pa.table({"x": rng.integers(2500, 7500, n),
                  "s": pa.array([f"v{i % 257}" for i in range(n)])})
    eng = QueryEngine()
    eng.register_table("a", a)
    eng.register_table("b", b)
    calls = []
    real = PE.replicate
    monkeypatch.setattr(PE, "replicate",
                        lambda batch, mesh_: calls.append(1) or
                        real(batch, mesh_))
    for sql in ("SELECT x, s FROM a INTERSECT SELECT x, s FROM b",
                "SELECT x, s FROM a EXCEPT SELECT x, s FROM b",
                "SELECT x FROM a UNION ALL SELECT x FROM b"):
        plan = eng.plan(sql)
        sh = ShardedExecutor(mesh=mesh)
        got = sh.execute_to_arrow(plan)
        want = eng.execute(sql)
        assert got.num_rows > 0, f"empty result would vacuously pass: {sql}"
        gd = sorted(tuple(r.values()) for r in got.to_pylist())
        wd = sorted(tuple(r.values()) for r in want.to_pylist())
        assert gd == wd, sql
    assert calls == [], "replicate() ran during sharded set ops"


def test_sharded_cross_join_gathers(engine, mesh):
    check(engine, mesh,
          "SELECT COUNT(*) AS c FROM (SELECT DISTINCT s FROM t) a, "
          "(SELECT DISTINCT grp FROM d) b")


# --- TPC-H end-to-end on the mesh ---

@pytest.mark.parametrize("q", ["q1", "q3", "q5", "q6", "q10", "q12"])
def test_sharded_tpch(q, mesh):
    from igloo_tpu.bench.tpch import QUERIES, gen_tables, register_all
    eng = QueryEngine()
    register_all(eng, gen_tables(sf=0.001))
    check(eng, mesh, QUERIES[q])


@pytest.mark.skipif(os.environ.get("IGLOO_FULL_TPCH") != "1",
                    reason="full 22-query sharded sweep (~10 min); set "
                           "IGLOO_FULL_TPCH=1 (scripts/validate.sh full tier)")
@pytest.mark.parametrize("q", [f"q{i}" for i in range(1, 23)])
def test_sharded_tpch_full(q, mesh):
    """Every TPC-H query, sharded-vs-single-device, on the virtual mesh.
    This is the suite-side counterpart of __graft_entry__.dryrun_multichip,
    which time-boxes itself under the driver's budget and so may not reach
    the tail queries."""
    from igloo_tpu.bench.tpch import QUERIES, gen_tables, register_all
    eng = QueryEngine()
    register_all(eng, gen_tables(sf=0.001))
    check(eng, mesh, QUERIES[q])


# --- round-4: range-partitioned sort + hash-partitioned distinct ------------

def test_sharded_sort_range_partitioned(engine, mesh):
    """Sharded ORDER BY must range-partition (no replicated gather): results
    equal AND no per-device lane exceeds 2x the local shard capacity."""
    from igloo_tpu.parallel.executor import ShardedExecutor
    plan = engine.plan("SELECT k, v FROM t ORDER BY v DESC, k")
    ex = ShardedExecutor(mesh=mesh)
    seen_caps = []
    orig = ShardedExecutor._sharded_sort

    def spy(self, p, batch):
        out = orig(self, p, batch)
        n = int(self.mesh.devices.size)
        local_in = batch.capacity // n
        local_out = out.capacity // n
        seen_caps.append((local_in, local_out))
        return out
    ShardedExecutor._sharded_sort = spy
    try:
        got = ex.execute_to_arrow(plan).to_pandas()
    finally:
        ShardedExecutor._sharded_sort = orig
    want = engine.execute("SELECT k, v FROM t ORDER BY v DESC, k").to_pandas()
    import pandas as pd
    pd.testing.assert_frame_equal(got.reset_index(drop=True),
                                  want.reset_index(drop=True))
    assert seen_caps, "sharded sort path did not run"
    for local_in, local_out in seen_caps:
        assert local_out <= 2 * local_in, (local_in, local_out)


def test_sharded_sort_skew_overflow_falls_back(engine, mesh):
    # 90% of rows share one key: range partitioning overflows its bucket and
    # the deferred flag must trigger an exact (gathered) re-run
    from igloo_tpu.parallel.executor import ShardedExecutor
    sql = "SELECT k, v FROM skew ORDER BY k, v"
    plan = engine.plan(sql)
    got = ShardedExecutor(mesh=mesh).execute_to_arrow(plan).to_pandas()
    want = engine.execute(sql).to_pandas()
    import pandas as pd
    pd.testing.assert_frame_equal(got.reset_index(drop=True),
                                  want.reset_index(drop=True))


def test_sharded_distinct_hash_partitioned(engine, mesh):
    from igloo_tpu.parallel.executor import ShardedExecutor
    sql = "SELECT DISTINCT k, s FROM t"
    plan = engine.plan(sql)
    ex = ShardedExecutor(mesh=mesh)
    seen = []
    orig = ShardedExecutor._sharded_distinct_of

    def spy(self, batch):
        out = orig(self, batch)
        n = int(self.mesh.devices.size)
        seen.append((batch.capacity // n, out.capacity // n))
        return out
    ShardedExecutor._sharded_distinct_of = spy
    try:
        got = ex.execute_to_arrow(plan).to_pandas()
    finally:
        ShardedExecutor._sharded_distinct_of = orig
    want = engine.execute(sql).to_pandas()
    key = ["k", "s"]
    import pandas as pd
    pd.testing.assert_frame_equal(
        got.sort_values(key).reset_index(drop=True),
        want.sort_values(key).reset_index(drop=True))
    assert seen, "sharded distinct path did not run"
    for local_in, local_out in seen:
        assert local_out <= 2 * local_in, (local_in, local_out)


def test_sharded_window_functions(engine, mesh):
    # inherited single-program path over row-sharded inputs: GSPMD inserts
    # the gathers; values must match the single-device engine exactly
    check(engine, mesh, """
        SELECT k, v, row_number() OVER (PARTITION BY k ORDER BY v) AS rn,
               sum(v) OVER (PARTITION BY k) AS s
        FROM t ORDER BY k, v
    """)
