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
# sha1 of repr(key[1]) (the node fingerprints) of _KEY_SQL over an ordinary
# MemTable named `facts`, taken on PR 34's tree. ISSUE 34 moved every key
# that holds an expression, once: an expression enters as `E.shape` (every
# field of every node, a literal's type and position but not its value)
# where it entered as its repr (`'(col(n) < lit(4))'`: no index, no type,
# the value). Everything else of the nodes is what PR 32 left.
_FPS_SHA1 = "18f3f357ce5b2ab2e4f4fd6cae3b67657bcce61a"


def _sha1(obj) -> str:
    import hashlib
    return hashlib.sha1(repr(obj).encode()).hexdigest()


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
    # content-light: neither the "k" column's content nor the value of the
    # filter's literal is in it (the literal's type is: `n < 4.0` differs)
    _, key = _compile_over(MemTable, _KEY_SQL, ("facts",))
    assert _sha1(key[1]) == _FPS_SHA1, repr(key[1])
    _, other = _compile_over(MemTable, _KEY_SQL.replace("< 4", "< 3"),
                             ("facts",))
    assert other == key
    _, typed = _compile_over(MemTable, _KEY_SQL.replace("< 4", "< 4.0"),
                             ("facts",))
    assert typed != key


# --- who decides that a hinted node compacts (ISSUE 31) ---

_SEL_N = 5000           # capacity 8192, over the lowered ADAPTIVE_CAPACITY
_SEL_GLOBAL = "SELECT sum(x * w) AS s, count(*) AS c FROM fact WHERE w < 3"
_SEL_PROJECTED = ("SELECT sum(y) AS s, count(*) AS c FROM "
                  "(SELECT x * w AS y FROM fact WHERE w < 3) t")
_SEL_GROUPED = ("SELECT fk, sum(x * w) AS s, count(*) AS c FROM fact "
                "WHERE w < 3 GROUP BY fk ORDER BY fk")
_SEL_JOINED = ("SELECT sum(x * w + v) AS s, count(*) AS c FROM fact "
               "JOIN dim ON fk = k WHERE w < 3")
# sha1 of repr(key[1]) (the node fingerprints) of _SEL_GROUPED's second
# compilation, its filter's hint adopted (`('acompact', 256)` after the
# filter node), taken on PR 34's tree (see _FPS_SHA1 for what moved)
_COMPACTED_FPS_SHA1 = "bc243be2fe669857ead8da9dbd880c4445599693"


def _sel_tables(dense: bool = False):
    """`fact` keeps 3 % of its rows under `w < 3` (dense: ~50 %, with the same
    capacity and bounds, so the same program and the same hint keys)."""
    rng = np.random.default_rng(3)
    w = rng.integers(0, 100, _SEL_N)
    if dense:
        w = np.where(rng.random(_SEL_N) < 0.5, w % 3, w)
        w[:2] = (0, 99)
    fact = pa.table({
        "fk": pa.array(rng.integers(1, 9, _SEL_N), type=pa.int64()),
        "w": pa.array(w, type=pa.int64()),
        "x": pa.array(rng.random(_SEL_N), type=pa.float64())})
    dim = pa.table({"k": pa.array(np.arange(1, 9), type=pa.int64()),
                    "v": pa.array(np.arange(8) * 1.5, type=pa.float64())})
    return fact, dim


def _sel_oracle(sql: str, fact: pa.Table, dim: pa.Table) -> dict:
    f = fact.to_pandas()
    f = f[f.w < 3].assign(y=lambda d: d.x * d.w)
    if sql == _SEL_GROUPED:
        g = f.groupby("fk").y.agg(["sum", "size"]).reset_index()
        return {"fk": list(g.fk), "s": list(g["sum"]), "c": list(g["size"])}
    if sql == _SEL_JOINED:
        f = f.merge(dim.to_pandas(), left_on="fk", right_on="k")
        return {"s": [float((f.y + f.v).sum())], "c": [len(f)]}
    return {"s": [float(f.y.sum())], "c": [len(f)]}


def _same_answer(t: pa.Table, want: dict):
    got = t.to_pydict()
    assert list(got) == list(want)
    for name in want:
        np.testing.assert_allclose(got[name], want[name], rtol=1e-12)


@pytest.fixture
def small_adaptive(monkeypatch):
    from igloo_tpu.exec.executor import Executor
    monkeypatch.setattr(F, "ADAPTIVE_CAPACITY", 1 << 10)
    monkeypatch.setattr(Executor, "_SPECULATIVE_JOIN_BUDGET", 1 << 10)


def _sel_engine(dense: bool = False):
    fact, dim = _sel_tables(dense)
    e = QueryEngine()
    e.register_table("fact", fact)
    e.register_table("dim", dim)
    return e, fact, dim


def _compile_on(e: QueryEngine, sql: str):
    """What the engine's next execution of `sql` would compile, its hints
    included: (compiler, program key)."""
    from igloo_tpu.exec.executor import Executor
    comp = F.FusedCompiler(Executor(e._jit_cache, batch_cache=e.batch_cache))
    _run, key, _meta = comp.compile(e.plan(sql))
    return comp, key


def _run_counting(e: QueryEngine, sql: str):
    e.result_cache.clear()
    tracing.reset_counters()
    t = e.execute(sql)
    return t, dict(tracing.counters())


@pytest.mark.parametrize("sql", [_SEL_GLOBAL, _SEL_PROJECTED],
                         ids=["filter_agg", "filter_project_agg"])
def test_global_aggregate_declines_its_filters_compaction(small_adaptive,
                                                          sql):
    e, fact, dim = _sel_engine()
    want = _sel_oracle(sql, fact, dim)
    _, key0 = _compile_on(e, sql)
    t, c = _run_counting(e, sql)
    _same_answer(t, want)
    assert c.get("jit.miss") == 1 and not c.get("fused.compact_declined")
    # the filter's live count is recorded as before: 3 % of 5000 rows, a
    # hint that WOULD compact 8192 lanes to 256
    hints = {k[1]: v for k, v in e._jit_cache.items()
             if isinstance(k, tuple) and k[0] == "nhint"}
    [(hkey, live)] = hints.items()
    assert hkey[0] == "filter" and live == want["c"][0] < 256

    # second execution, the hint present: the same program, found again
    t, c = _run_counting(e, sql)
    _same_answer(t, want)
    assert c.get("jit.hit") == 1 and not c.get("jit.miss")
    assert c.get("fused.compact_declined") == 1
    assert not c.get("fused.compact_repair")
    comp, key = _compile_on(e, sql)
    assert key == key0
    assert not [fp for fp in comp.fps if fp[0] == "acompact"]
    assert not [tag for tag in comp.flag_tags if tag[0] == "compact"]
    assert comp.stat_keys == [hkey]


@pytest.mark.parametrize("sql", [_SEL_GROUPED, _SEL_JOINED],
                         ids=["grouped_aggregate", "join"])
def test_other_consumers_still_get_a_compacted_filter(small_adaptive, sql):
    e, fact, dim = _sel_engine()
    want = _sel_oracle(sql, fact, dim)
    t, c = _run_counting(e, sql)
    _same_answer(t, want)
    t, c = _run_counting(e, sql)
    _same_answer(t, want)
    assert c.get("jit.miss") == 1            # the hinted program
    assert not c.get("fused.compact_declined")
    comp, key = _compile_on(e, sql)
    assert ("acompact", 256) in comp.fps
    assert [tag[0] for tag in comp.flag_tags if tag[1][0] == "filter"] \
        == ["compact"]
    if sql == _SEL_GROUPED:
        assert [fp[0] for fp in comp.fps] == [
            "scan", "filter", "acompact", "agg", "project", "sort"]
        assert _sha1(key[1]) == _COMPACTED_FPS_SHA1, repr(key[1])


def test_declined_filter_needs_no_repair_when_its_data_grows(small_adaptive):
    e, fact, dim = _sel_engine()
    sparse = _sel_oracle(_SEL_GLOBAL, fact, dim)
    for _ in range(2):
        t, c = _run_counting(e, _SEL_GLOBAL)
    _same_answer(t, sparse)
    # same capacity and bounds, 16 x the live rows: an adopted hint of 256
    # lanes would overflow and pay a repair re-run; a declined one has
    # nothing to repair
    dense, _ = _sel_tables(dense=True)
    e.register_table("fact", dense)
    want = _sel_oracle(_SEL_GLOBAL, dense, dim)
    assert want["c"][0] > 16 * sparse["c"][0]
    t, c = _run_counting(e, _SEL_GLOBAL)
    _same_answer(t, want)
    assert not c.get("fused.compact_repair") and not c.get("jit.miss")
    assert c.get("fused.compact_declined") == 1


@pytest.mark.parametrize("sql,compacts", [
    (_SEL_GLOBAL, 0), (_SEL_PROJECTED, 0), (_SEL_GROUPED, 1)],
    ids=["filter_agg", "filter_project_agg", "grouped_aggregate"])
def test_staged_executor_asks_the_same_predicate(small_adaptive, sql,
                                                 compacts):
    from igloo_tpu.exec.executor import Executor
    e, fact, dim = _sel_engine()
    want = _sel_oracle(sql, fact, dim)
    plan = e.plan(sql)
    for _ in range(2):      # first sight records the live count, then adopts
        tracing.reset_counters()
        ex = Executor(e._jit_cache, batch_cache=e.batch_cache)
        _same_answer(ex._staged_to_arrow(plan), want)
    assert tracing.counters().get("join.input_compact", 0) == compacts


# sha1 of repr(key[1:5]) (node fingerprints, pool signature, marks, fetch
# capacity) of the program each benchmark query settles on at SF 0.01 under
# the lowered thresholds, and of repr(sorted(repr(k) for the engine's nhint
# keys)), taken on PR 34's tree. Both moved once with ISSUE 34 (expressions
# enter as `E.shape`, and the pool's signature gained the literals' scalar
# vectors); hint keys persist as digests (nhints.json), so a store re-learns
# its hints once. What must hold from here on: the digests do not move with
# a query's substitution parameters (the last lines of the test).
_KEY_SHA1 = {"q3": "9ed4e63a6c17884e17c5c3b44139fc680d8fca38",
             "q1": "dbe5763c310b20b1e1b86f0386b236f474d27536"}
_HINT_KEYS_SHA1 = {"q3": "139fef303a521d3bd27ad7e741235d27c700ee66",
                   "q1": "5f5d0c50f43ff0f7b01abd5e0ade452ad151a97d"}


def _hint_keys(e: QueryEngine) -> list:
    """The engine's hint keys, sorted reprs."""
    return sorted(repr(k) for k in e._jit_cache
                  if isinstance(k, tuple) and k[0] == "nhint")


@pytest.mark.parametrize("q", sorted(_KEY_SHA1))
def test_program_keys_of_the_bypass_queries_did_not_move(small_adaptive, q):
    from igloo_tpu.bench.tpch import QUERIES, gen_tables, register_all
    e = QueryEngine()
    register_all(e, gen_tables(sf=0.01))
    for _ in range(3):                       # cold, hinted, steady
        _t, c = _run_counting(e, QUERIES[q])
    assert c.get("jit.hit") == 1 and not c.get("jit.miss")
    assert not c.get("fused.compact_declined")
    comp, key = _compile_on(e, QUERIES[q])
    if q == "q3":
        assert [fp for fp in comp.fps if fp[0] == "acompact"]
    assert len(key) == 5
    assert _sha1(key[1:5]) == _KEY_SHA1[q], repr(key[1:5])
    assert _sha1(_hint_keys(e)) == _HINT_KEYS_SHA1[q]
    # another parameter set of the query (TPC-H clauses 2.4.1.3, 2.4.3.3):
    # the same program, under the hints the first set learnt
    other = QUERIES[q].replace("'90' DAY", "'68' DAY") \
        .replace("1995-03-15", "1995-03-20")
    assert other != QUERIES[q]
    assert _compile_on(e, other)[1] == key


def test_sorted_join_lost_its_plans_and_kept_its_hint_key(monkeypatch):
    # a float key takes no direct table, so the join is `join_sorted`; the
    # digests are PR 34's (see _KEY_SHA1 for what moved them)
    monkeypatch.setattr(F, "ADAPTIVE_CAPACITY", 1 << 10)
    rng = np.random.default_rng(5)
    e = QueryEngine()
    e.register_table("a", pa.table({
        "fk": pa.array(rng.integers(0, 16, 5000) * 0.5, type=pa.float64()),
        "x": pa.array(rng.random(5000), type=pa.float64())}))
    e.register_table("b", pa.table({
        "k": pa.array(np.arange(8) * 0.5, type=pa.float64()),
        "v": pa.array(np.arange(8), type=pa.int64())}))
    sql = ("SELECT v, COUNT(*) AS c FROM a JOIN b ON a.fk = b.k "
           "GROUP BY v ORDER BY v")
    for _ in range(2):
        t, c = _run_counting(e, sql)
    assert sum(t.to_pydict()["c"]) == int(
        (e.execute("SELECT COUNT(*) AS n FROM a WHERE fk < 4")
         .to_pydict()["n"][0]))
    comp, key = _compile_on(e, sql)
    [jfp] = [fp for fp in comp.fps if fp[0] == "join_sorted"]
    # ends with the match capacity and the output schema: no plan rides
    assert jfp[-2] == 8192 and type(jfp[-1]).__name__ == "Schema"
    assert _sha1(key[1:5]) == "4dd1351adeb052c88cfda60a5155650d645bb0c6", repr(key[1:5])
    [hkey] = [k for k in comp.stat_keys if k[0] == "join"]
    assert hkey[1][-1][0] == "join_sorted"
    assert _sha1(hkey) == "a33f34fc472ea3b4c66d25595bab3d2d63cb2fc6"


def _sorted_join_engine():
    # float keys take no direct table: both compilers plan a sorted probe
    # join, whose expand phase has two routes to a slot's probe row. Keys
    # that match nothing (zero-count probe rows), runs of duplicates on both
    # sides and NULL keys are the cases the routes must agree on
    rng = np.random.default_rng(11)
    fk = rng.integers(0, 24, 3000) * 0.5
    a = pa.table({"fk": pa.array(fk, type=pa.float64(),
                                 mask=np.arange(3000) % 97 == 0),
                  "x": pa.array(np.arange(3000), type=pa.int64())})
    k = np.repeat(np.arange(16) * 0.5, 3)
    b = pa.table({"k": pa.array(k, type=pa.float64(),
                                mask=np.arange(48) % 13 == 0),
                  "v": pa.array(np.arange(48), type=pa.int64())})
    e = QueryEngine()
    e.register_table("a", a)
    e.register_table("b", b)
    return e, a.to_pandas(), b.to_pandas()


@pytest.mark.parametrize("how", ["inner", "left"])
@pytest.mark.parametrize("route", ["scan", "search"])
@pytest.mark.parametrize("compiler", ["fused", "staged"])
def test_both_match_routes_through_both_compilers(monkeypatch, compiler,
                                                  route, how):
    # off the TPU both compilers plan the searchsorted inversion; the
    # scatter + cummax scan is what they plan on the chip. Each is held to
    # pandas here, through the compiler that passes it on
    from igloo_tpu.exec import executor as X
    from igloo_tpu.exec import join as J
    assert J.match_by_search() is True          # XLA:CPU
    calls = []

    def choice():
        calls.append(route)
        return route == "search"
    monkeypatch.setattr(F, "match_by_search", choice)
    monkeypatch.setattr(X, "match_by_search", choice)
    e, a, b = _sorted_join_engine()
    sql = (f"SELECT x, v FROM a {how.upper()} JOIN b ON a.fk = b.k "
           "ORDER BY x, v")
    if compiler == "fused":
        got = e.execute(sql)
        comp, _key = _compile_on(e, sql)
        assert [fp for fp in comp.fps if fp[0] == "join_sorted"]
    else:
        ex = X.Executor(e._jit_cache, batch_cache=e.batch_cache)
        got = ex._staged_to_arrow(e.plan(sql))
    assert calls
    want = a.merge(b.dropna(subset=["k"]), left_on="fk", right_on="k",
                   how=how).sort_values(["x", "v"])
    assert got.column("x").to_pylist() == want.x.tolist()
    assert [None if v is None else int(v)
            for v in got.column("v").to_pylist()] == \
        [None if np.isnan(v) else int(v) for v in want.v.tolist()]


def test_nofuse_sentinel_is_armed_once_per_program(tmp_path):
    # ISSUE 32 took the mode token out of the program key, so `_fused_run`
    # finds its program in the cache from the second execution on, as its
    # comment says: the sentinel (two rewrites of nhints.json) is armed and
    # cleared before a program's FIRST execution in the process only
    from igloo_tpu.exec.hints import HintStore
    fact, dim = _mk_tables(N_FACT, 1000, match_every=64)
    e = QueryEngine()
    e.hint_store = store = HintStore(str(tmp_path / "nhints.json"))
    e.register_table("fact", fact)
    e.register_table("dim", dim)
    armed = []
    put = store.put

    def counting_put(key, n):
        if key[0] == "nofuse":
            armed.append(key)
        put(key, n)
    store.put = counting_put
    for _ in range(4):                       # cold, hinted, steady, steady
        _t, c = _run_counting(e, SQL)
    assert c.get("jit.hit") and not c.get("jit.miss")
    programs = {k for k in e._jit_cache
                if isinstance(k, tuple) and k[0] == "fused"}
    assert len(armed) == len(set(armed)) == len(programs) >= 2
    # and cleared once its program has run
    assert [store.get(k) for k in armed] == [None] * len(armed)


def test_chunked_global_partial_declines_too(small_adaptive, tmp_path):
    # LocalChunkExecutor runs each chunk's partial aggregate through the same
    # compiler: every chunk's global partial over a selective filter keeps
    # its lanes, and no hinted second program is compiled for any of them
    import pyarrow.parquet as pq
    from igloo_tpu.connectors.parquet import ParquetTable
    fact, dim = _sel_tables()
    big = pa.concat_tables([fact] * 4)
    path = str(tmp_path / "fact.parquet")
    pq.write_table(big, path, row_group_size=2500)
    e = QueryEngine(chunk_budget_bytes=1 << 16)
    e.register_table("fact", ParquetTable(path))
    want = _sel_oracle(_SEL_GLOBAL, big, dim)
    for _ in range(3):      # the second execution settles the chunking
        t, c = _run_counting(e, _SEL_GLOBAL)
        _same_answer(t, want)
    assert c.get("engine.chunked_route") and not c.get("jit.miss")
    assert c.get("fused.compact_declined") == c.get("fused.execute") >= 2


# --- a wide plan seen for the first time probes its counts --------------------

def _runs(probe_capacity: int, monkeypatch, fact, dim, n: int = 3):
    """n executions of SQL on a fresh engine without persistent hints ->
    ([counter deltas], [the program key its next execution compiles to],
    the last answer)."""
    monkeypatch.setattr(F, "PROBE_CAPACITY", probe_capacity)
    e = QueryEngine()
    e.hint_store = None
    e.register_table("fact", fact)
    e.register_table("dim", dim)
    deltas, keys = [], []
    for _ in range(n):
        e.result_cache.clear()
        with tracing.counter_delta() as d:
            t = e.execute(SQL)
        deltas.append(d.values())
        keys.append(F.FusedCompiler(e._executor()).compile(e.plan(SQL))[1])
    return deltas, keys, (t.column("s")[0].as_py(), t.column("c")[0].as_py())


@pytest.mark.parametrize("match_every", [64, 1])
def test_a_wide_plan_probes_its_counts_then_compiles_the_hinted_program(
        monkeypatch, match_every):
    """Below PROBE_CAPACITY (the default here) the first execution compiles
    and runs the unhinted program and the second the hinted one; above it
    the first execution runs the probe — the live counts alone — and
    compiles the hinted program at once: the same program, the same answer,
    one execution sooner. Where no hint shrinks anything (every row
    matches) the hinted program is the unhinted one."""
    fact, dim = _mk_tables(N_FACT, 1000, match_every=match_every)
    want = _oracle(fact, dim)
    plain, plain_keys, got = _runs(F.PROBE_CAPACITY, monkeypatch, fact, dim)
    assert got == want
    assert [d.get("jit.miss", 0) for d in plain] == [1, 1 if match_every > 1
                                                     else 0, 0]
    assert not any(d.get("fused.probe") for d in plain)
    probed, probed_keys, got = _runs(F.ADAPTIVE_CAPACITY, monkeypatch, fact,
                                     dim)
    assert got == want
    assert probed[0]["fused.probe"] == 1
    assert probed[0]["jit.miss"] == 2          # the probe, the hinted program
    assert probed[0]["fused.execute"] == 1     # one program ran the query
    assert [d.get("jit.miss", 0) for d in probed[1:]] == [0, 0]
    assert not any(d.get("fused.probe") for d in probed[1:])
    assert probed_keys[0] == plain_keys[-1]
    assert not any(d.get("fused.compact_repair") for d in plain + probed)


GROUPED = ("SELECT w * 1.5 AS g, count(*) AS c FROM fact JOIN dim ON fk = k "
           "GROUP BY w * 1.5 ORDER BY g")


def test_above_a_wide_candidate_a_grouping_node_never_compacts(monkeypatch):
    """100 groups over 2^20 joined lanes: the aggregate's hint would shrink
    its output 8192-fold. Below PROBE_CAPACITY the unhinted program's run
    learns it and the second execution compiles the program that compacts
    by it; above it the probe leaves grouping counts out and the count the
    hinted program records is never adopted, so the plan settles on the
    program its first execution compiled, with the same rows."""
    fact, dim = _mk_tables(N_FACT, 1000, match_every=1)
    answers, default = [], F.PROBE_CAPACITY
    for probe_capacity, misses in ((default, [1, 1, 0]),
                                   (F.ADAPTIVE_CAPACITY, [2, 0, 0])):
        monkeypatch.setattr(F, "PROBE_CAPACITY", probe_capacity)
        e = QueryEngine()
        e.hint_store = None
        e.register_table("fact", fact)
        e.register_table("dim", dim)
        got = []
        for _ in range(3):
            e.result_cache.clear()
            with tracing.counter_delta() as d:
                t = e.execute(GROUPED)
            got.append(d.get("jit.miss"))
        comp = F.FusedCompiler(e._executor())
        comp.compile(e.plan(GROUPED))
        assert got == misses
        compacted = [fp for fp in comp.fps if fp[0] == "acompact"]
        assert bool(compacted) == (probe_capacity == default)
        assert comp.wide == (probe_capacity != default)
        answers.append(t.to_pydict())
    assert answers[0] == answers[1] and len(answers[0]["g"]) == 100
