"""Device kernel tests: aggregate, join, sort, limit — checked against
pandas/pyarrow oracles on the CPU backend (SURVEY.md §4 test plan (a))."""
import numpy as np
import pyarrow as pa
import pytest

from igloo_tpu import types as T
from igloo_tpu.exec.aggregate import AggSpec, aggregate_batch, distinct_batch
from igloo_tpu.exec.batch import DeviceBatch, from_arrow, to_arrow
from igloo_tpu.exec.expr_compile import Compiled, ExprCompiler
from igloo_tpu.exec.join import _probe_bounds, expand_phase, join_batches
from igloo_tpu.exec.sort_limit import limit_batch, plan_topk, sort_batch
from igloo_tpu.plan.expr import AggFunc, BinOp, Binary, Column
from igloo_tpu.sql.ast import JoinType


def col(batch: DeviceBatch, i: int) -> Compiled:
    f = batch.schema.fields[i]
    return Compiled(lambda env, _i=i: (env.values[_i], env.nulls[_i]),
                    f.dtype, batch.columns[i].dictionary)


def out_schema_for(groups, aggs, batch, names):
    fields = []
    for g, n in zip(groups, names[: len(groups)]):
        fields.append(T.Field(n, g.dtype, True))
    for a, n in zip(aggs, names[len(groups):]):
        fields.append(T.Field(n, a.out_dtype, True))
    return T.Schema(fields)


class TestAggregate:
    def test_group_sum_count(self):
        t = pa.table({
            "k": ["a", "b", "a", "c", "b", "a"],
            "v": pa.array([1, 2, 3, 4, 5, 6], type=pa.int64()),
        })
        b = from_arrow(t)
        g = [col(b, 0)]
        aggs = [AggSpec(AggFunc.SUM, col(b, 1), T.INT64, None),
                AggSpec(AggFunc.COUNT_STAR, None, T.INT64, None)]
        schema = out_schema_for(g, aggs, b, ["k", "s", "c"])
        out = to_arrow(aggregate_batch(b, g, aggs, schema)).to_pydict()
        got = dict(zip(out["k"], zip(out["s"], out["c"])))
        assert got == {"a": (10, 3), "b": (7, 2), "c": (4, 1)}

    def test_min_max_avg_with_nulls(self):
        t = pa.table({
            "k": pa.array([1, 1, 2, 2, 2], type=pa.int32()),
            "v": pa.array([5.0, None, 1.0, 3.0, None]),
        })
        b = from_arrow(t)
        g = [col(b, 0)]
        aggs = [AggSpec(AggFunc.MIN, col(b, 1), T.FLOAT64, None),
                AggSpec(AggFunc.MAX, col(b, 1), T.FLOAT64, None),
                AggSpec(AggFunc.AVG, col(b, 1), T.FLOAT64, None),
                AggSpec(AggFunc.COUNT, col(b, 1), T.INT64, None)]
        schema = out_schema_for(g, aggs, b, ["k", "mn", "mx", "av", "ct"])
        out = to_arrow(aggregate_batch(b, g, aggs, schema)).to_pydict()
        got = {k: (mn, mx, av, ct) for k, mn, mx, av, ct in
               zip(out["k"], out["mn"], out["mx"], out["av"], out["ct"])}
        assert got[1] == (5.0, 5.0, 5.0, 1)
        assert got[2] == (1.0, 3.0, 2.0, 2)

    def test_all_null_group_sum_is_null(self):
        t = pa.table({"k": [1, 1], "v": pa.array([None, None], type=pa.float64())})
        b = from_arrow(t)
        aggs = [AggSpec(AggFunc.SUM, col(b, 1), T.FLOAT64, None)]
        schema = out_schema_for([col(b, 0)], aggs, b, ["k", "s"])
        out = to_arrow(aggregate_batch(b, [col(b, 0)], aggs, schema)).to_pydict()
        assert out["s"] == [None]

    def test_global_aggregate_empty_input(self):
        t = pa.table({"v": pa.array([], type=pa.int64())})
        b = from_arrow(t)
        aggs = [AggSpec(AggFunc.COUNT_STAR, None, T.INT64, None),
                AggSpec(AggFunc.SUM, col(b, 0), T.INT64, None)]
        schema = out_schema_for([], aggs, b, ["c", "s"])
        out = to_arrow(aggregate_batch(b, [], aggs, schema)).to_pydict()
        assert out["c"] == [0]
        assert out["s"] == [None]

    def test_null_group_key_is_one_group(self):
        t = pa.table({"k": pa.array([1, None, None, 1], type=pa.int64()),
                      "v": pa.array([1, 2, 3, 4], type=pa.int64())})
        b = from_arrow(t)
        aggs = [AggSpec(AggFunc.SUM, col(b, 1), T.INT64, None)]
        schema = out_schema_for([col(b, 0)], aggs, b, ["k", "s"])
        out = to_arrow(aggregate_batch(b, [col(b, 0)], aggs, schema)).to_pydict()
        got = dict(zip(out["k"], out["s"]))
        assert got == {1: 5, None: 5}

    def test_min_max_string_group(self):
        t = pa.table({"k": [1, 1, 2], "s": ["zeta", "alpha", "mid"]})
        b = from_arrow(t)
        aggs = [AggSpec(AggFunc.MIN, col(b, 1), T.STRING,
                        b.columns[1].dictionary),
                AggSpec(AggFunc.MAX, col(b, 1), T.STRING,
                        b.columns[1].dictionary)]
        schema = out_schema_for([col(b, 0)], aggs, b, ["k", "mn", "mx"])
        out = to_arrow(aggregate_batch(b, [col(b, 0)], aggs, schema)).to_pydict()
        got = {k: (mn, mx) for k, mn, mx in zip(out["k"], out["mn"], out["mx"])}
        assert got == {1: ("alpha", "zeta"), 2: ("mid", "mid")}

    def test_distinct(self):
        t = pa.table({"a": [1, 2, 1, 2, 3], "b": ["x", "y", "x", "z", "x"]})
        b = from_arrow(t)
        out = to_arrow(distinct_batch(b))
        rows = set(zip(out.column("a").to_pylist(), out.column("b").to_pylist()))
        assert rows == {(1, "x"), (2, "y"), (2, "z"), (3, "x")}

    def test_large_random_groups_vs_pandas(self):
        rng = np.random.default_rng(42)
        n = 5000
        k = rng.integers(0, 97, n)
        v = rng.normal(size=n)
        t = pa.table({"k": pa.array(k, type=pa.int64()), "v": v})
        b = from_arrow(t)
        aggs = [AggSpec(AggFunc.SUM, col(b, 1), T.FLOAT64, None),
                AggSpec(AggFunc.COUNT_STAR, None, T.INT64, None)]
        schema = out_schema_for([col(b, 0)], aggs, b, ["k", "s", "c"])
        out = to_arrow(aggregate_batch(b, [col(b, 0)], aggs, schema))
        import pandas as pd
        expect = pd.DataFrame({"k": k, "v": v}).groupby("k").agg(
            s=("v", "sum"), c=("v", "size"))
        got = out.to_pandas().set_index("k").sort_index()
        assert (got["c"] == expect["c"]).all()
        np.testing.assert_allclose(got["s"], expect["s"], rtol=1e-9)


class TestJoin:
    @pytest.fixture(autouse=True, params=["scan", "search"])
    def _route(self, request):
        # both ways expand_phase finds a slot's probe row, each held to the
        # same expectations: the scatter + cummax scan is what the compilers
        # plan on a TPU, the searchsorted inversion what they plan here
        self._search = request.param == "search"

    def _join(self, lt, rt, jt, n_keys=1, residual=None, out_names=None,
              pool=None):
        lb, rb = from_arrow(lt), from_arrow(rt)
        lk = [col(lb, i) for i in range(n_keys)]
        rk = [col(rb, i) for i in range(n_keys)]
        if jt in (JoinType.SEMI, JoinType.ANTI):
            schema = lb.schema
        else:
            fields = list(lb.schema.fields) + [
                T.Field(f"r_{f.name}", f.dtype, True) for f in rb.schema.fields]
            schema = T.Schema(fields)

        def expand(l, r, p, match_cap, consts):
            return expand_phase(l, r, p, match_cap, jt, residual, schema,
                                consts, match_search=self._search)
        return to_arrow(join_batches(lb, rb, lk, rk, jt, residual, schema,
                                     expand_jit=expand, pool=pool))

    def test_inner_with_duplicates(self):
        lt = pa.table({"k": pa.array([1, 2, 2, 3], type=pa.int64()),
                       "lv": pa.array([10, 20, 21, 30], type=pa.int64())})
        rt = pa.table({"k": pa.array([2, 2, 3, 4], type=pa.int64()),
                       "rv": pa.array([200, 201, 300, 400], type=pa.int64())})
        out = self._join(lt, rt, JoinType.INNER)
        rows = sorted(zip(out.column("lv").to_pylist(),
                          out.column("r_rv").to_pylist()))
        assert rows == [(20, 200), (20, 201), (21, 200), (21, 201), (30, 300)]

    def test_left_outer(self):
        lt = pa.table({"k": pa.array([1, 2], type=pa.int64()),
                       "lv": pa.array([10, 20], type=pa.int64())})
        rt = pa.table({"k": pa.array([2], type=pa.int64()),
                       "rv": pa.array([200], type=pa.int64())})
        out = self._join(lt, rt, JoinType.LEFT)
        rows = sorted(zip(out.column("lv").to_pylist(),
                          out.column("r_rv").to_pylist()),
                      key=lambda r: r[0])
        assert rows == [(10, None), (20, 200)]

    def test_right_and_full_outer_emit_unmatched_right(self):
        # the reference never emits unmatched build-side rows (gap G4); we must
        lt = pa.table({"k": pa.array([1], type=pa.int64()),
                       "lv": pa.array([10], type=pa.int64())})
        rt = pa.table({"k": pa.array([1, 7], type=pa.int64()),
                       "rv": pa.array([100, 700], type=pa.int64())})
        out = self._join(lt, rt, JoinType.RIGHT)
        rows = sorted(zip(out.column("lv").to_pylist(),
                          out.column("r_rv").to_pylist()),
                      key=lambda r: (r[0] is None, r))
        assert rows == [(10, 100), (None, 700)]
        out = self._join(lt, rt, JoinType.FULL)
        assert out.num_rows == 2  # 1 matched + 1 right-unmatched (+0 left-unmatched)

    def test_null_keys_never_match(self):
        lt = pa.table({"k": pa.array([1, None], type=pa.int64()),
                       "lv": pa.array([10, 20], type=pa.int64())})
        rt = pa.table({"k": pa.array([1, None], type=pa.int64()),
                       "rv": pa.array([100, 200], type=pa.int64())})
        out = self._join(lt, rt, JoinType.INNER)
        assert out.num_rows == 1
        assert out.column("lv").to_pylist() == [10]

    def test_semi_anti(self):
        lt = pa.table({"k": pa.array([1, 2, 3], type=pa.int64()),
                       "lv": pa.array([10, 20, 30], type=pa.int64())})
        rt = pa.table({"k": pa.array([2, 2], type=pa.int64())})
        semi = self._join(lt, rt, JoinType.SEMI)
        assert semi.column("lv").to_pylist() == [20]
        anti = self._join(lt, rt, JoinType.ANTI)
        assert sorted(anti.column("lv").to_pylist()) == [10, 30]

    def test_null_aware_anti_not_in(self):
        # NOT IN desugars (binder) to a key-less anti join with residual
        # "x = y OR y IS NULL OR x IS NULL"; with a NULL on the right it keeps
        # nothing, without it it behaves like plain anti
        from igloo_tpu.plan.expr import IsNull

        def not_in_residual(lb, rb):
            x = Column("k", index=0)
            x.dtype = T.INT64
            y = Column("k", index=len(lb.schema))
            y.dtype = T.INT64
            eq = Binary(op=BinOp.EQ, left=x, right=y)
            eq.dtype = T.BOOL
            yn = IsNull(operand=y)
            yn.dtype = T.BOOL
            xn = IsNull(operand=x)
            xn.dtype = T.BOOL
            o1 = Binary(op=BinOp.OR, left=eq, right=yn)
            o1.dtype = T.BOOL
            o2 = Binary(op=BinOp.OR, left=o1, right=xn)
            o2.dtype = T.BOOL
            dicts = [c.dictionary for c in lb.columns] + \
                    [c.dictionary for c in rb.columns]
            compiler = ExprCompiler(dicts)
            return compiler.compile(o2), compiler.pool

        lt = pa.table({"k": pa.array([1, 2], type=pa.int64()),
                       "lv": pa.array([10, 20], type=pa.int64())})
        rt = pa.table({"k": pa.array([2, None], type=pa.int64())})
        lb, rb = from_arrow(lt), from_arrow(rt)
        res, pool = not_in_residual(lb, rb)
        out = to_arrow(join_batches(lb, rb, [], [], JoinType.ANTI,
                                    res, lb.schema, pool=pool))
        assert out.num_rows == 0
        rt2 = pa.table({"k": pa.array([2], type=pa.int64())})
        rb2 = from_arrow(rt2)
        res2, pool2 = not_in_residual(lb, rb2)
        out2 = to_arrow(join_batches(lb, rb2, [], [], JoinType.ANTI,
                                     res2, lb.schema, pool=pool2))
        assert out2.column("lv").to_pylist() == [10]

    def test_string_keys_across_dictionaries(self):
        lt = pa.table({"s": ["apple", "pear", "kiwi"],
                       "lv": pa.array([1, 2, 3], type=pa.int64())})
        rt = pa.table({"s": ["pear", "apple", "mango"],
                       "rv": pa.array([20, 10, 40], type=pa.int64())})
        out = self._join(lt, rt, JoinType.INNER)
        rows = sorted(zip(out.column("lv").to_pylist(),
                          out.column("r_rv").to_pylist()))
        assert rows == [(1, 10), (2, 20)]

    def test_multi_key(self):
        lt = pa.table({"a": pa.array([1, 1, 2], type=pa.int64()),
                       "b": ["x", "y", "x"],
                       "lv": pa.array([10, 11, 20], type=pa.int64())})
        rt = pa.table({"a": pa.array([1, 2], type=pa.int64()),
                       "b": ["y", "x"],
                       "rv": pa.array([100, 200], type=pa.int64())})
        out = self._join(lt, rt, JoinType.INNER, n_keys=2)
        rows = sorted(zip(out.column("lv").to_pylist(),
                          out.column("r_rv").to_pylist()))
        assert rows == [(11, 100), (20, 200)]

    def test_cross_join(self):
        lt = pa.table({"a": pa.array([1, 2], type=pa.int64())})
        rt = pa.table({"b": pa.array([10, 20, 30], type=pa.int64())})
        out = self._join(lt, rt, JoinType.CROSS, n_keys=0)
        assert out.num_rows == 6

    def test_residual_filter(self):
        lt = pa.table({"k": pa.array([1, 1], type=pa.int64()),
                       "lv": pa.array([5, 15], type=pa.int64())})
        rt = pa.table({"k": pa.array([1], type=pa.int64()),
                       "rv": pa.array([10], type=pa.int64())})
        # residual: lv < rv  (combined schema: k, lv, r_k, r_rv)
        lc = Column("lv", index=1)
        lc.dtype = T.INT64
        rc = Column("rv", index=3)
        rc.dtype = T.INT64
        pred = Binary(op=BinOp.LT, left=lc, right=rc)
        pred.dtype = T.BOOL
        lb, rb = from_arrow(lt), from_arrow(rt)
        compiler = ExprCompiler([c.dictionary for c in lb.columns] +
                                [c.dictionary for c in rb.columns])
        comp = compiler.compile(pred)
        out = self._join(lt, rt, JoinType.INNER, residual=comp,
                         pool=compiler.pool)
        assert out.column("lv").to_pylist() == [5]

    def test_large_join_vs_pandas(self):
        rng = np.random.default_rng(7)
        lk = rng.integers(0, 200, 3000)
        rk = rng.integers(0, 200, 1000)
        lt = pa.table({"k": pa.array(lk, type=pa.int64()),
                       "lv": pa.array(np.arange(3000), type=pa.int64())})
        rt = pa.table({"k": pa.array(rk, type=pa.int64()),
                       "rv": pa.array(np.arange(1000), type=pa.int64())})
        out = self._join(lt, rt, JoinType.INNER)
        import pandas as pd
        expect = pd.merge(lt.to_pandas(), rt.to_pandas(), on="k")
        assert out.num_rows == len(expect)
        got = sorted(zip(out.column("lv").to_pylist(),
                         out.column("r_rv").to_pylist()))
        want = sorted(zip(expect["lv"], expect["rv"]))
        assert got == want


class TestSortLimit:
    def test_multi_key_sort_with_nulls(self):
        t = pa.table({
            "a": pa.array([2, 1, 2, None, 1], type=pa.int64()),
            "b": pa.array([1.0, 9.0, None, 5.0, 3.0]),
        })
        b = from_arrow(t)
        out = to_arrow(sort_batch(b, [col(b, 0), col(b, 1)],
                                  [True, False], [False, False]))
        # a asc nulls last; within a: b desc nulls last
        assert out.column("a").to_pylist() == [1, 1, 2, 2, None]
        assert out.column("b").to_pylist() == [9.0, 3.0, 1.0, None, 5.0]

    def test_sort_desc_string(self):
        t = pa.table({"s": ["b", "c", "a"]})
        b = from_arrow(t)
        out = to_arrow(sort_batch(b, [col(b, 0)], [False], [False]))
        assert out.column("s").to_pylist() == ["c", "b", "a"]

    def test_limit_offset(self):
        t = pa.table({"v": pa.array(range(10), type=pa.int64())})
        b = from_arrow(t)
        out = to_arrow(limit_batch(b, 3, offset=2))
        assert out.column("v").to_pylist() == [2, 3, 4]

    def test_sort_stability(self):
        t = pa.table({"k": pa.array([1, 1, 1, 1], type=pa.int64()),
                      "v": pa.array([4, 3, 2, 1], type=pa.int64())})
        b = from_arrow(t)
        out = to_arrow(sort_batch(b, [col(b, 0)], [True], [False]))
        assert out.column("v").to_pylist() == [4, 3, 2, 1]  # original order kept


# --- probe bounds: the one combined sort against numpy's searchsorted -------

_I64_MAX = np.iinfo(np.int64).max


def _mixed_build(seed, m, spread):
    """Half live keys, a quarter of those one displaced-NULL sentinel run,
    the rest the dead rows' MAX-sentinel run (join.probe_phase's layout)."""
    rng = np.random.default_rng(seed)
    live = m // 2
    return np.concatenate([
        rng.integers(-spread, spread, live - live // 4),
        np.full(live // 4, 0x0FEDCBA987654321),
        np.full(m - live, _I64_MAX)]).astype(np.int64)


@pytest.mark.parametrize("build,n,spread", [
    (_mixed_build(0, 512, 400), 256, 400),
    (_mixed_build(1, 256, 50), 512, 50),          # runs of ~2 per key
    (_mixed_build(2, 1024, 100000), 128, 100000),  # almost no match
    (np.full(128, _I64_MAX, np.int64), 64, 100),  # empty (all-dead) build
    (np.zeros(256, np.int64), 64, 1),             # one key, a run of 256
], ids=["mixed", "duplicates", "sparse", "empty_build", "all_one_key"])
def test_probe_bounds_match_searchsorted(build, n, spread):
    import jax.numpy as jnp
    probe = np.random.default_rng(9).integers(
        -spread, spread, n).astype(np.int64)
    lo, up = _probe_bounds(jnp.asarray(build), jnp.asarray(probe))
    # hashes compare with the low bit dropped (the side tag's place);
    # masking preserves the sort order
    sb = np.sort(build) & np.int64(-2)
    p = probe & np.int64(-2)
    np.testing.assert_array_equal(np.asarray(lo),
                                  np.searchsorted(sb, p, side="left"))
    np.testing.assert_array_equal(np.asarray(up),
                                  np.searchsorted(sb, p, side="right"))


# --- ORDER BY + LIMIT: the lax.top_k route against the full sort ------------

@pytest.mark.parametrize("cap,k,pack,n_keys,want", [
    (1024, 13, (("i32", 0, ()), 2), 2, True),
    (1024, 0, (("i32", 0, ()), 2), 2, False),      # LIMIT 0
    (1024, 13, (("i32", 0, ()), 1), 2, False),     # a key left unpacked
    (1024, 13, None, 2, False),
    (64, 100, (("i32", 0, ()), 2), 2, False),      # LIMIT covers the batch
    (64, 32, (("i32", 0, ()), 2), 2, True),        # 2k == cap still pays
], ids=["adopts", "limit0", "partial_pack", "no_pack", "large_limit",
        "half"])
def test_plan_topk_rule(cap, k, pack, n_keys, want):
    from igloo_tpu.utils import tracing
    with tracing.counter_delta() as d:
        assert plan_topk(cap, k, pack, n_keys) is want
    assert d.get("topk.alg") == int(want)


def _sort_engine(n=900, seed=5):
    from igloo_tpu.engine import QueryEngine
    rng = np.random.default_rng(seed)
    e = QueryEngine()
    e.register_table("t", pa.table({
        "a": pa.array(rng.integers(0, 40, n), type=pa.int64()),
        "b": pa.array([None if v < 30 else int(v)
                       for v in rng.integers(0, 300, n)], type=pa.int64()),
        "x": pa.array(rng.normal(size=n)),
    }))
    return e


_FULL_SQL = "SELECT a, b, x FROM t ORDER BY a, b"


def _first_k(t: pa.Table, k: int):
    return [tuple(c[i] for c in t.to_pydict().values()) for i in range(k)]


def test_topk_alg_route_on_kernels_off_tier():
    """ORDER BY + LIMIT over packable keys takes lax.top_k (topk.alg) and
    reproduces the full stable sort's first k rows — heavy duplicate keys
    (ties) and NULLs included."""
    from igloo_tpu.utils import tracing
    full = _sort_engine().execute(_FULL_SQL)
    with tracing.counter_delta() as d:
        got = _sort_engine().execute(_FULL_SQL + " LIMIT 13")
    assert d.get("topk.alg") > 0
    assert got.num_rows == 13
    assert _first_k(got, 13) == _first_k(full, 13)


def test_topk_offset_rows():
    full = _sort_engine().execute(_FULL_SQL)
    got = _sort_engine().execute(_FULL_SQL + " LIMIT 10 OFFSET 5")
    assert got.num_rows == 10
    assert _first_k(got, 10) == _first_k(full, 15)[5:]


def test_limit_ge_rows_takes_direct_path():
    """Regression: a LIMIT covering most of the batch must NOT route through
    the partial top-k (2*k > capacity buys nothing): the full sort runs."""
    from igloo_tpu.utils import tracing
    full = _sort_engine(n=60).execute(_FULL_SQL)
    with tracing.counter_delta() as d:
        got = _sort_engine(n=60).execute(_FULL_SQL + " LIMIT 100")
    assert d.get("topk.alg") == 0
    assert got.num_rows == 60
    assert _first_k(got, 60) == _first_k(full, 60)
