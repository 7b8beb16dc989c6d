"""Whole-plan fusion (exec/fused.py): hint adoption, stale-hint repair,
duplicate-key negative cache, and fused-vs-staged result equality.

The fused path is the default executor route; these tests drive the adaptive
capacity-hint machinery explicitly across repeated executions and data changes
— states the single-run TPC-H suite never reaches."""
import numpy as np
import pyarrow as pa
import pytest

from igloo_tpu.catalog import MemTable
from igloo_tpu.engine import QueryEngine
from igloo_tpu.exec import fused as F
from igloo_tpu.utils import tracing


def _mk_tables(n_fact: int, n_dim: int, match_every: int, seed: int = 3):
    """Fact/dim pair: fact.fk hits dim.k for one row in `match_every`
    (others point at key 0, absent from dim: k starts at 1)."""
    rng = np.random.default_rng(seed)
    fk = np.where(np.arange(n_fact) % match_every == 0,
                  rng.integers(1, n_dim + 1, n_fact), 0)
    fact = pa.table({
        "fk": pa.array(fk, type=pa.int64()),
        "w": pa.array(rng.integers(0, 100, n_fact), type=pa.int64()),
    })
    dim = pa.table({
        "k": pa.array(np.arange(1, n_dim + 1), type=pa.int64()),
        "v": pa.array(rng.integers(0, 100, n_dim), type=pa.int64()),
    })
    return fact, dim


# big enough that the join/filter outputs clear ADAPTIVE_CAPACITY
N_FACT = F.ADAPTIVE_CAPACITY * 2 + 17
SQL = "SELECT sum(w + v) AS s, count(*) AS c FROM fact JOIN dim ON fk = k"


def _oracle(fact: pa.Table, dim: pa.Table):
    f = fact.to_pandas()
    d = dim.to_pandas()
    j = f.merge(d, left_on="fk", right_on="k")
    return int((j.w + j.v).sum()), len(j)


def test_hint_adoption_and_stale_hint_repair():
    fact, dim = _mk_tables(N_FACT, 1000, match_every=64)
    e = QueryEngine()
    e.register_table("fact", fact)
    e.register_table("dim", dim)

    s, c = _oracle(fact, dim)
    # run 1: no hints -> eager full-width join, records cardinalities
    tracing.reset_counters()
    t = e.execute(SQL)
    assert (t.column("s")[0].as_py(), t.column("c")[0].as_py()) == (s, c)
    assert tracing.counters().get("fused.execute", 0) >= 1
    hints = [k for k in e._jit_cache if isinstance(k, tuple) and k[0] == "nhint"]
    assert hints, "expected cardinality hints after the first run"

    # run 2: hinted lazy/compacted program, same answer
    e.result_cache.clear()
    tracing.reset_counters()
    t = e.execute(SQL)
    assert (t.column("s")[0].as_py(), t.column("c")[0].as_py()) == (s, c)
    assert not tracing.counters().get("fused.compact_repair")

    # same shapes/bounds but ~16x more matches: the stale hint under-sizes the
    # compaction, the overflow flag fires, and ONE repair re-run fixes it
    fact2, _ = _mk_tables(N_FACT, 1000, match_every=4, seed=3)
    e.register_table("fact", fact2)
    s2, c2 = _oracle(fact2, dim)
    assert c2 > 4 * c
    e.result_cache.clear()
    tracing.reset_counters()
    t = e.execute(SQL)
    assert (t.column("s")[0].as_py(), t.column("c")[0].as_py()) == (s2, c2)
    assert tracing.counters().get("fused.compact_repair", 0) == 1

    # run 4: hints refreshed, no repair
    e.result_cache.clear()
    tracing.reset_counters()
    t = e.execute(SQL)
    assert (t.column("s")[0].as_py(), t.column("c")[0].as_py()) == (s2, c2)
    assert not tracing.counters().get("fused.compact_repair")


def test_duplicate_build_keys_negative_cache():
    # build side (smaller, dense bounds) has duplicate keys -> the direct
    # attempt must flag, fall back exactly, and not be retried next run
    dup_dim = pa.table({
        "k": pa.array([1, 1, 2, 3, 4, 5, 6, 7], type=pa.int64()),
        "v": pa.array([10, 11, 20, 30, 40, 50, 60, 70], type=pa.int64()),
    })
    fact = pa.table({
        "fk": pa.array([1, 2, 2, 5, 9], type=pa.int64()),
        "w": pa.array([1, 2, 3, 4, 5], type=pa.int64()),
    })
    e = QueryEngine()
    e.register_table("fact", fact)
    e.register_table("dim", dup_dim)
    sql = "SELECT fk, w, v FROM fact JOIN dim ON fk = k ORDER BY fk, w, v"
    want = {"fk": [1, 1, 2, 2, 5], "w": [1, 1, 2, 3, 4],
            "v": [10, 11, 20, 20, 50]}

    tracing.reset_counters()
    t = e.execute(sql)
    assert t.to_pydict() == want
    assert tracing.counters().get("join.direct_dup_fallback", 0) >= 1
    assert any(isinstance(k, tuple) and k[0] == "nodirect"
               for k in e._jit_cache)

    # the negative cache is PER SIDE: the next run may probe the other side
    # (also duplicated here) and fall back once more — but results stay exact
    e.result_cache.clear()
    t = e.execute(sql)
    assert t.to_pydict() == want

    # both sides proven duplicated: sorted path compiled up front, no fallback
    e.result_cache.clear()
    tracing.reset_counters()
    t = e.execute(sql)
    assert t.to_pydict() == want
    assert not tracing.counters().get("join.direct_dup_fallback")


@pytest.mark.parametrize("jointype,exp", [
    ("JOIN", {"fk": [1, 2, 2], "w": [1, 2, 3], "v": [10, 20, 20]}),
    ("LEFT JOIN", {"fk": [1, 2, 2, 5, 9], "w": [1, 2, 3, 4, 5],
                   "v": [10, 20, 20, None, None]}),
])
def test_fused_matches_staged(jointype, exp):
    dim = pa.table({"k": pa.array([1, 2, 3], type=pa.int64()),
                    "v": pa.array([10, 20, 30], type=pa.int64())})
    fact = pa.table({"fk": pa.array([1, 2, 2, 5, 9], type=pa.int64()),
                     "w": pa.array([1, 2, 3, 4, 5], type=pa.int64())})
    e = QueryEngine()
    e.register_table("fact", fact)
    e.register_table("dim", dim)
    sql = f"SELECT fk, w, v FROM fact {jointype} dim ON fk = k ORDER BY w"
    t = e.execute(sql)
    assert t.to_pydict() == exp
    # force the staged route for the identical plan
    from igloo_tpu.exec.executor import Executor
    ex = Executor(e._jit_cache, batch_cache=e.batch_cache)
    t2 = ex._staged_to_arrow(e.plan(sql))
    assert t2.to_pydict() == exp


# --- what a scan contributes to the program key (ISSUE 29) ---

class _OneExecutionTable(MemTable):
    """What the worker hands a fragment for each dependency: a provider that
    declares itself the input of one execution."""
    ephemeral = True


_KEY_SQL = ("SELECT k, SUM(v) AS s, COUNT(*) AS c FROM {0} "
            "WHERE n < 4 GROUP BY k ORDER BY k")
# aliased, as a fragment's expressions keep the statement's qualifiers
_KEY_JOIN_SQL = ("SELECT a.k, SUM(a.v + b.v) AS s FROM {0} a "
                 "JOIN {1} b ON a.n = b.n GROUP BY a.k ORDER BY 1")
# repr of key[1] (the node fingerprints) of _KEY_SQL over an ordinary
# MemTable named `facts`, taken on the parent commit (2149ae4)
_PARENT_FPS = (
    "(('scan', 'facts', (), '[(col(n) < lit(4))]', None, "
    "Schema(k: string, v: float64, n: int64), 8, (True, False, False), "
    "(None, None, (0, 8)), (('int8', ('int32', False, 1.0, False)), "
    "('int8', ('float64', False, 1.0, False)), "
    "('int8', ('int64', False, 1.0, False)))), "
    "('filter', '(col(n) < lit(4))'), "
    "('agg', ('col(k)', 'col(v)'), ((<AggFunc.SUM: 'sum'>, float64), "
    "(<AggFunc.COUNT_STAR: 'count_star'>, int64)), "
    "Schema(k: string, __agg_0: float64, __agg_1: int64), ((3, 0),), "
    "None, None), "
    "('project', ('col(k)', 'col(__agg_0)', 'col(__agg_1)'), "
    "Schema(k: string, s: float64, c: int64)), "
    "('sort', ('col(k)',), (True,), (False,), "
    "(('i32', 0, ((4, True, False),)), 1)))")


def _compile_over(provider_cls, sql: str, names: tuple):
    """Compile `sql` over one `provider_cls` table per name (same schema and
    capacity, different content); returns the FusedCompiler and its key."""
    from igloo_tpu.exec.executor import Executor
    e = QueryEngine()
    for i, name in enumerate(names):
        e.register_table(name, provider_cls(pa.table({
            "k": pa.array([name, "b", name, None]),
            "v": [1.0 + i, 2.0, 3.0, 4.0],
            "n": pa.array([1, 2, 3, 4], pa.int64())})))
    comp = F.FusedCompiler(Executor())
    _run, key, _meta = comp.compile(e.plan(sql.format(*names)))
    return comp, key


@pytest.mark.parametrize("sql,first,second", [
    (_KEY_SQL, ("dep_3f9a",), ("dep_c071",)),
    (_KEY_JOIN_SQL, ("dep_3f9a", "dep_77e2"), ("dep_c071", "dep_0b1d")),
], ids=["one_leaf", "two_leaves"])
def test_ephemeral_scan_is_keyed_by_position(sql, first, second):
    c1, k1 = _compile_over(_OneExecutionTable, sql, first)
    c2, k2 = _compile_over(_OneExecutionTable, sql, second)
    assert k1 == k2 and c1.hfps == c2.hfps
    scans = [fp for fp in c1.fps if fp[0] == "scan"]
    assert [fp[1] for fp in scans] == list(range(len(first)))


def test_ordinary_scan_is_keyed_by_name():
    _, k1 = _compile_over(MemTable, _KEY_SQL, ("facts",))
    _, k2 = _compile_over(MemTable, _KEY_SQL, ("fakts",))
    assert k1 != k2


def test_ordinary_scan_fingerprint_did_not_move():
    # the "k" column's content differs from the parent's sample ("a"): the
    # key is content-light, so the literal still holds
    _, key = _compile_over(MemTable, _KEY_SQL, ("facts",))
    assert repr(key[1]) == _PARENT_FPS
