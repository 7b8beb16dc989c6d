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


# --- the one-pass small-domain segment reduce (ISSUE 33) ---

_SCATTER = {"sum": "segment_sum", "min": "segment_min", "max": "segment_max"}


def _reduce_lanes(n, nseg, seed):
    """Seeded lanes of the three dtypes an aggregate reduces, a segment lane
    whose dead rows sit in the last slot, and the ids live rows can take."""
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    ids = list(range(1, nseg - 1, 2)) or [0]
    live = rng.random(n) < 0.8
    seg = np.where(live, rng.choice(ids, n), nseg - 1).astype(np.int32)
    f64 = jnp.asarray(rng.standard_normal(n) * 1e6)
    i64 = jnp.asarray(rng.integers(-2**40, 2**40, n))
    i32 = jnp.asarray(rng.integers(-1000, 1000, n).astype(np.int32))
    return f64, i64, i32, jnp.asarray(seg), ids


def _scatter_ref(v, op, seg, nseg, ids):
    """jax.ops.segment_<op> on the produced ids, the identity elsewhere."""
    import jax
    from igloo_tpu.exec import kernels as K
    ref = np.asarray(getattr(jax.ops, _SCATTER[op])(v, seg, num_segments=nseg))
    ident = np.asarray(K._SEG_OPS[op].identity(v.dtype))
    return np.where(np.isin(np.arange(nseg), ids), ref, ident)


@pytest.mark.parametrize("nseg", [8, 16, 32, 64])
def test_seg_reduce_equals_scatter_on_mixed_lanes(nseg):
    """float64, int64 and int32 lanes, sums and extremes, in ONE call: each
    result equals jax.ops.segment_* on the feasible ids, keeps its lane's
    dtype, and reads the op's identity in every slot not produced (the dead
    rows' slot among them)."""
    from igloo_tpu.exec import kernels as K
    f64, i64, i32, seg, ids = _reduce_lanes(4096, nseg, seed=nseg)
    lanes = [(f64, "sum"), (i64, "sum"), (i32, "sum"), (f64, "min"),
             (i64, "max"), (i32, "min"), (f64, "max")]
    outs = K.seg_reduce(lanes, seg, nseg, ids)
    assert len(outs) == len(lanes)
    for (v, op), got in zip(lanes, outs):
        assert got.shape == (nseg,) and got.dtype == v.dtype
        want = _scatter_ref(v, op, seg, nseg, ids)
        if v.dtype == np.float64 and op == "sum":
            np.testing.assert_allclose(np.asarray(got), want, rtol=1e-12)
        else:
            assert np.array_equal(np.asarray(got), want)


@pytest.mark.parametrize("nseg", [8, 16, 32, 64])
def test_seg_reduce_keeps_nan_and_inf_in_their_segment(nseg):
    """NaN, +inf and -inf rows of one segment must not reach another's sum,
    minimum or maximum (a cumsum-difference formulation would leak them)."""
    import jax.numpy as jnp
    from igloo_tpu.exec import kernels as K
    f64, _, _, seg, ids = _reduce_lanes(2048, nseg, seed=100 + nseg)
    bad = ids[0]
    vals = np.asarray(f64).copy()
    rows = np.flatnonzero(np.asarray(seg) == bad)[:3]
    vals[rows] = [np.nan, np.inf, -np.inf]
    v = jnp.asarray(vals)
    s, lo, hi = K.seg_reduce([(v, "sum"), (v, "min"), (v, "max")], seg, nseg,
                             ids)
    assert np.isnan(np.asarray(s)[bad])
    others = [i for i in ids if i != bad]
    for got, op in ((s, "sum"), (lo, "min"), (hi, "max")):
        want = _scatter_ref(v, op, seg, nseg, ids)
        assert np.all(np.isfinite(np.asarray(got)[others]))
        np.testing.assert_allclose(np.asarray(got)[others], want[others],
                                   rtol=1e-12)


@pytest.mark.parametrize("op,ident", [("sum", 0), ("min", np.iinfo(np.int64).max),
                                      ("max", np.iinfo(np.int64).min)])
def test_seg_reduce_with_no_feasible_segment_reads_identities(op, ident):
    from igloo_tpu.exec import kernels as K
    _, i64, _, seg, _ = _reduce_lanes(256, 16, seed=5)
    [got] = K.seg_reduce([(i64, op)], seg, 16, [])
    assert np.asarray(got).tolist() == [ident] * 16


def test_seg_reduce_duplicate_lanes_and_chunked_operands(monkeypatch):
    """A lane handed over twice is reduced twice to the same numbers, and a
    lane list cut into several reduces (a low operand bound, so one lane's
    segments straddle two of them) equals the uncut one bit for bit on
    integer lanes."""
    from igloo_tpu.exec import kernels as K
    f64, i64, i32, seg, ids = _reduce_lanes(4096, 32, seed=9)
    lanes = [(f64, "sum"), (i64, "sum"), (f64, "sum"), (i32, "max"),
             (i64, "sum")]
    whole = K.seg_reduce(lanes, seg, 32, ids)
    assert np.array_equal(np.asarray(whole[0]), np.asarray(whole[2]))
    assert np.array_equal(np.asarray(whole[1]), np.asarray(whole[4]))
    monkeypatch.setattr(K, "MAX_REDUCE_OPERANDS", 7)
    cut = K.seg_reduce(lanes, seg, 32, ids)
    for a, b in zip(whole, cut):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-12)
    assert np.array_equal(np.asarray(whole[1]), np.asarray(cut[1]))


# --- float64 sums folded as f32 pairs (ISSUE 39) ----------------------------

_PAIR_LANES = 1 << 20
_PAIR_IDS = [5, 6, 7, 9, 10, 11]   # q1's feasible ids of a padded 16
_PAIR_BOUND = 1e-12                # relative to sum(|x|), at 2^20 lanes


def _pair_case(name):
    """(values, segment lane) of one case: TPC-H-shaped lanes, mixed signs,
    magnitudes over twenty decades, structure cases, non-finite rows."""
    rng = np.random.default_rng(39)
    n = _PAIR_LANES
    price = np.round(rng.uniform(900.0, 105000.0, n), 2)
    disc = rng.integers(0, 11, n) / 100.0
    tax = rng.integers(0, 9, n) / 100.0
    seg = np.where(rng.random(n) < 0.98, rng.choice(_PAIR_IDS, n),
                   15).astype(np.int32)
    charge = price * (1 - disc) * (1 + tax)
    if name == "price":
        v = price
    elif name == "disc_price":
        v = price * (1 - disc)
    elif name == "charge":
        v = charge
    elif name == "discounts":
        v = disc
    elif name == "mixed_signs":
        v = rng.standard_normal(n) * 1e4
    elif name == "twenty_decades":
        v = rng.standard_normal(n) * 10.0 ** rng.integers(-10, 10, n)
    elif name == "empty_segment":
        v = charge
        seg = np.where(seg == _PAIR_IDS[2], 15, seg).astype(np.int32)
    elif name == "all_dead":
        v = charge
        seg = np.full(n, 15, np.int32)
    else:
        v = charge.copy()
        rows = np.flatnonzero(seg == _PAIR_IDS[1])
        v[rows[:4]] = {"pos_inf": [np.inf, 1.0, np.inf, 2.0],
                       "nan": [np.nan, 1.0, 2.0, 3.0],
                       "both_infs": [np.inf, 1.0, -np.inf, 2.0]}[name]
    return v, seg


def _np_halves(v):
    """The f32 halves a pair carrier would hold; a non-finite row keeps its
    value in `hi` beside a zero, as the f32 round-trip carrier holds it."""
    with np.errstate(invalid="ignore", over="ignore"):
        hi = v.astype(np.float32)
        lo = (v - hi.astype(np.float64)).astype(np.float32)
    return hi, np.where(np.isfinite(hi), lo, np.float32(0))


@pytest.mark.parametrize("route", ["split_in_trace", "halves_given"])
@pytest.mark.parametrize("case", [
    "price", "disc_price", "charge", "discounts", "mixed_signs",
    "twenty_decades", "empty_segment", "all_dead", "pos_inf", "nan",
    "both_infs"])
def test_pair_fold_equals_the_exact_sum(case, route):
    """`seg_reduce`'s pair fold, engaged as the chip's canary would engage
    it, against `math.fsum` of the values the halves stand for: within 1e-12
    of sum(|x|) at 2^20 lanes in XLA:CPU's order, for halves split in-trace
    and for halves handed over. A lane on which the float64 arm itself errs
    more (the discounts: eleven distinct values, whose roundings into ANY
    accumulator do not cancel) is held to 1.5 x that arm's error. A segment
    with a non-finite row reads what the float64 arm reads; a segment or a
    lane without rows reads 0."""
    import math
    import jax
    import jax.numpy as jnp
    from igloo_tpu.exec import kernels as K
    v, seg = _pair_case(case)
    hi, lo = _np_halves(v)
    with np.errstate(invalid="ignore"):
        x = np.where(np.isfinite(v), hi.astype(np.float64) + lo, v)
    segj = jnp.asarray(seg)
    [wide] = jax.jit(lambda a, s: K.seg_reduce(
        [(a, "sum")], s, 16, _PAIR_IDS))(jnp.asarray(x), segj)
    if route == "split_in_trace":
        [got] = jax.jit(lambda a, s: K.seg_reduce(
            [(a, "sum")], s, 16, _PAIR_IDS, pair_sums=True))(
                jnp.asarray(x), segj)
    else:
        [got] = jax.jit(lambda h, l, s: K.seg_reduce(
            [((h, l), "sum")], s, 16, _PAIR_IDS))(
                jnp.asarray(hi), jnp.asarray(lo), segj)
    assert got.shape == (16,) and got.dtype == jnp.float64
    got, wide = np.asarray(got), np.asarray(wide)
    for i in range(16):
        rows = x[seg == i] if i in _PAIR_IDS else x[:0]
        if not np.isfinite(rows).all():
            assert np.array_equal(got[i], wide[i], equal_nan=True), (i, got[i])
        elif len(rows) == 0:
            assert got[i] == 0.0
        else:
            want, mag = math.fsum(rows), math.fsum(np.abs(rows))
            bound = max(_PAIR_BOUND, 1.5 * abs(wide[i] - want) / mag)
            assert abs(got[i] - want) / mag <= bound, (i, got[i], want)


def test_pair_fold_needs_its_renormalising_lines(monkeypatch):
    """`pair_add`'s last two lines are not an ornament: folded without them
    (two_sum of the high halves, the low halves added up), 2^20 charges in
    ONE segment miss the bound the renormalised fold keeps on the same
    lane — `lo` grows and rounds at its own size."""
    import math
    import jax.numpy as jnp
    from igloo_tpu.exec import kernels as K
    v, _ = _pair_case("charge")
    hi, lo = _np_halves(v)
    x = hi.astype(np.float64) + lo
    want, mag = math.fsum(x), math.fsum(np.abs(x))
    seg = jnp.zeros(len(v), jnp.int32)
    lanes = [((jnp.asarray(hi), jnp.asarray(lo)), "sum")]

    def err():
        [got] = K.seg_reduce(lanes, seg, 8, [0])
        return abs(float(got[0]) - want) / mag
    assert err() <= _PAIR_BOUND

    def unrenormalised(x, y):
        s, e = K.two_sum(x[0], y[0])
        return s, e + x[1] + y[1]
    monkeypatch.setattr(K, "pair_add", unrenormalised)
    assert err() > 10 * _PAIR_BOUND


def test_pair_fold_counts_a_pair_once_against_the_operand_bound(monkeypatch):
    """Five pair lanes and a count over six segments are 36 accumulators
    (66 f32 operands): ONE `lax.reduce` under the bound of 48; cut at a
    lower bound, the same numbers. Above SMALL_NSEG halves handed over are
    widened and scattered."""
    import jax
    import jax.numpy as jnp
    from igloo_tpu.exec import kernels as K
    rng = np.random.default_rng(3)
    n = 4096
    seg = jnp.asarray(rng.choice(_PAIR_IDS, n).astype(np.int32))
    vs = [rng.uniform(1.0, 1e5, n) for _ in range(5)]
    lanes = [(jnp.asarray(v), "sum") for v in vs] + \
        [(jnp.ones(n, jnp.int32), "sum")]

    def run(s):
        return K.seg_reduce(lanes, s, 16, _PAIR_IDS, pair_sums=True)
    assert str(jax.make_jaxpr(run)(seg)).count(" reduce[") == 1
    whole = run(seg)
    monkeypatch.setattr(K, "MAX_REDUCE_OPERANDS", 7)
    assert str(jax.make_jaxpr(lambda s: run(s))(seg)).count(" reduce[") == 6
    for a, b, v in zip(whole, run(seg), vs + [np.ones(n)]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-12)
        want = [v[np.asarray(seg) == i].sum() for i in _PAIR_IDS]
        np.testing.assert_allclose(np.asarray(a)[_PAIR_IDS], want, rtol=1e-12)
    hi, lo = _np_halves(vs[0])
    big = jnp.asarray(rng.integers(0, 128, n).astype(np.int32))
    [got] = K.seg_reduce([((jnp.asarray(hi), jnp.asarray(lo)), "sum")], big,
                         128)
    want = jax.ops.segment_sum(jnp.asarray(hi.astype(np.float64) + lo), big,
                               num_segments=128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-14)


@pytest.mark.parametrize("wrapper,op", [("seg_sum", "sum"), ("seg_min", "min"),
                                        ("seg_max", "max")])
@pytest.mark.parametrize("nseg", [64, 128])
def test_seg_wrappers_on_both_sides_of_the_threshold(wrapper, op, nseg):
    """K.seg_sum / seg_min / seg_max: the one-pass reduce over every id at
    SMALL_NSEG, the scatter above it; the same numbers either way."""
    import jax
    from igloo_tpu.exec import kernels as K
    f64, _, _, seg, _ = _reduce_lanes(2048, nseg, seed=nseg + 1)
    got = getattr(K, wrapper)(f64, seg, nseg)
    want = getattr(jax.ops, _SCATTER[op])(f64, seg, num_segments=nseg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-12)


def ccol(batch: DeviceBatch, i: int) -> Compiled:
    """Column `i` compiled from its bound expression, as the executors do:
    it carries `expr`, so that equal arguments share their lanes (`col`
    builds its Compiled by hand and shares with nothing)."""
    f = batch.schema.fields[i]
    e = Column(f.name, index=i)
    e.dtype = f.dtype
    return ExprCompiler.for_batch(batch).compile(e)


def _rows(batch):
    """A result batch as a sorted list of row tuples (NaN-safe repr)."""
    d = to_arrow(batch).to_pydict()
    return sorted(zip(*d.values()), key=repr)


def _direct_and_sorted(t, group_idx, mk_aggs, names):
    """aggregate_batch over `t` twice: on the direct path (seg_dims from the
    keys) and on the sort path (seg_dims=None), with the counters the direct
    trace bumped."""
    from igloo_tpu.exec.aggregate import seg_dims_for
    from igloo_tpu.utils import tracing
    b = from_arrow(t)
    g = [col(b, i) for i in group_idx]
    aggs = mk_aggs(b)
    schema = out_schema_for(g, aggs, b, names)
    dims = seg_dims_for(g)
    assert dims is not None
    with tracing.counter_delta() as d:
        direct = aggregate_batch(b, g, aggs, schema, seg_dims=dims)
    return direct, aggregate_batch(b, g, aggs, schema), d, dims


def _q1_like(n=500, seed=3, null_keys=False, null_vals=False):
    rng = np.random.default_rng(seed)
    flag = rng.choice(["A", "N", "R"], n).tolist()
    status = rng.choice(["F", "O"], n).tolist()
    qty = rng.integers(1, 51, n).astype(np.float64)
    price = np.round(rng.uniform(900, 105000, n), 2)
    if null_keys:
        flag[::7] = [None] * len(flag[::7])
    qty_arr = pa.array(qty, mask=(np.arange(n) % 5 == 0) if null_vals
                       else None)
    return pa.table({"flag": flag, "status": status, "qty": qty_arr,
                     "price": pa.array(price)})


def _q1_aggs(b, col=ccol):
    # each spec compiles its argument anew, as the executors do: the lanes
    # are shared by what the expressions compute, not by object identity
    qty, price = (lambda: col(b, 2)), (lambda: col(b, 3))
    return [AggSpec(AggFunc.SUM, qty(), T.FLOAT64, None),
            AggSpec(AggFunc.SUM, price(), T.FLOAT64, None),
            AggSpec(AggFunc.AVG, qty(), T.FLOAT64, None),
            AggSpec(AggFunc.AVG, price(), T.FLOAT64, None),
            AggSpec(AggFunc.COUNT, qty(), T.INT64, None),
            AggSpec(AggFunc.MIN, price(), T.FLOAT64, None),
            AggSpec(AggFunc.MAX, qty(), T.FLOAT64, None),
            AggSpec(AggFunc.COUNT_STAR, None, T.INT64, None)]


_Q1_NAMES = ["flag", "status", "sq", "sp", "aq", "ap", "cq", "mp", "xq", "n"]


class TestDirectAggregate:
    def test_key_without_null_lane_yields_no_null_group(self):
        """Two dictionary keys without null lanes: 6 feasible segments of a
        padded 16, no NULL group, the sort path's rows; SUM and AVG of one
        argument share a lane and COUNT(*) shares the live count."""
        direct, by_sort, d, dims = _direct_and_sorted(
            _q1_like(), [0, 1], _q1_aggs, _Q1_NAMES)
        assert dims == ((4, 0), (3, 0)) and direct.capacity == 16
        assert d.get("agg.onepass_segments") == 6
        # live count, sum(qty), sum(price) | min(price), max(qty) | 2 positions
        assert d.get("agg.onepass_lanes") == 3 + 2 + 2
        got = _rows(direct)
        assert len(got) == 6 and all(r[0] is not None and r[1] is not None
                                     for r in got)
        want = _rows(by_sort)
        for g, w in zip(got, want):
            assert g[:2] == w[:2] and g[6:] == w[6:]
            np.testing.assert_allclose(g[2:6], w[2:6], rtol=1e-12)

    def test_key_with_null_lane_keeps_its_null_group(self):
        direct, by_sort, d, _ = _direct_and_sorted(
            _q1_like(null_keys=True), [0, 1], _q1_aggs, _Q1_NAMES)
        # digit 0 of `flag` is feasible now: 4 x 2 ids
        assert d.get("agg.onepass_segments") == 8
        got, want = _rows(direct), _rows(by_sort)
        assert sum(r[0] is None for r in got) == 2 and len(got) == 8
        for g, w in zip(got, want):
            assert g[:2] == w[:2] and g[6:] == w[6:]
            np.testing.assert_allclose(g[2:6], w[2:6], rtol=1e-12)

    def test_null_arguments_count_apart_from_live_rows(self):
        """An argument with a null lane gets a valid-count lane of its own;
        its COUNT differs from COUNT(*), both equal the sort path's."""
        direct, by_sort, d, _ = _direct_and_sorted(
            _q1_like(null_vals=True), [0, 1], _q1_aggs, _Q1_NAMES)
        assert d.get("agg.onepass_lanes") == 4 + 2 + 2
        got, want = _rows(direct), _rows(by_sort)
        assert any(r[6] != r[9] for r in got)
        for g, w in zip(got, want):
            assert g[:2] == w[:2] and g[6:] == w[6:]
            np.testing.assert_allclose(g[2:6], w[2:6], rtol=1e-12)

    def test_all_null_argument_is_sum_null_count_zero(self):
        t = pa.table({"k": ["a", "b", "a", "b"],
                      "v": pa.array([None, 1.5, None, 2.5])})

        def aggs(b):
            v = ccol(b, 1)
            return [AggSpec(AggFunc.SUM, v, T.FLOAT64, None),
                    AggSpec(AggFunc.COUNT, v, T.INT64, None),
                    AggSpec(AggFunc.AVG, v, T.FLOAT64, None),
                    AggSpec(AggFunc.MIN, v, T.FLOAT64, None),
                    AggSpec(AggFunc.COUNT_STAR, None, T.INT64, None)]
        direct, by_sort, _, _ = _direct_and_sorted(
            t, [0], aggs, ["k", "s", "c", "a", "m", "n"])
        assert _rows(direct) == _rows(by_sort) == [
            ("a", None, 0, None, None, 2), ("b", 4.0, 2, 2.0, 1.5, 2)]

    def test_specs_without_fingerprint_share_nothing_and_agree(self):
        """Arguments built by hand (`Compiled.expr` is None, as the mesh
        tier's final stage builds them): every spec reduces lanes of its
        own; the answers are the fingerprinted ones."""
        def bare(b):
            return _q1_aggs(b, col=col)
        named, _, d1, _ = _direct_and_sorted(_q1_like(), [0, 1], _q1_aggs,
                                             _Q1_NAMES)
        alone, _, d2, _ = _direct_and_sorted(_q1_like(), [0, 1], bare,
                                             _Q1_NAMES)
        assert d2.get("agg.onepass_lanes") > d1.get("agg.onepass_lanes")
        assert _rows(named) == _rows(alone)

    def test_min_max_nan_winner_on_the_direct_path(self):
        """MAX over floats orders NaN above +inf and returns the NaN itself
        (exact gather of the winning row), per group, as the sort path does."""
        t = pa.table({"k": ["a", "a", "b", "b", "b"],
                      "v": pa.array([1.0, float("nan"), float("inf"), 2.0,
                                     -3.0])})

        def aggs(b):
            v = ccol(b, 1)
            return [AggSpec(AggFunc.MAX, v, T.FLOAT64, None),
                    AggSpec(AggFunc.MIN, v, T.FLOAT64, None)]
        direct, by_sort, _, _ = _direct_and_sorted(t, [0], aggs,
                                                   ["k", "mx", "mn"])
        got = to_arrow(direct).to_pydict()
        by = dict(zip(got["k"], zip(got["mx"], got["mn"])))
        assert np.isnan(by["a"][0]) and by["a"][1] == 1.0
        assert by["b"] == (float("inf"), -3.0)
        assert repr(_rows(direct)) == repr(_rows(by_sort))

    def test_dense_integer_domain_above_the_threshold_still_scatters(self):
        """A key domain over SMALL_NSEG segments keeps jax.ops.segment_*:
        no one-pass counter moves, the answers equal the sort path's."""
        rng = np.random.default_rng(11)
        n = 2000
        t = pa.table({"k": pa.array(rng.integers(0, 300, n), type=pa.int32()),
                      "v": pa.array(rng.standard_normal(n))})

        def aggs(b):
            v = ccol(b, 1)
            return [AggSpec(AggFunc.SUM, v, T.FLOAT64, None),
                    AggSpec(AggFunc.AVG, v, T.FLOAT64, None),
                    AggSpec(AggFunc.MAX, v, T.FLOAT64, None)]
        from igloo_tpu.exec.aggregate import seg_dims_for
        from igloo_tpu.utils import tracing
        b = from_arrow(t)
        g = [Compiled(col(b, 0).fn, T.INT32, None, out_bounds=(0, 299))]
        schema = out_schema_for(g, aggs(b), b, ["k", "s", "a", "m"])
        dims = seg_dims_for(g)
        assert dims == ((301, 0),)
        with tracing.counter_delta() as d:
            direct = aggregate_batch(b, g, aggs(b), schema, seg_dims=dims)
        assert not d.get("agg.onepass_segments")
        assert not d.get("agg.onepass_lanes")
        want = _rows(aggregate_batch(b, g, aggs(b), schema))
        for gr, w in zip(_rows(direct), want):
            assert gr[0] == w[0] and gr[3] == w[3]
            np.testing.assert_allclose(gr[1:3], w[1:3], rtol=1e-12)


# --- what identifies an aggregate argument's lane (REVIEW of PR 33) ---

def _bound(e, dtype):
    e.dtype = dtype
    return e


def _like_flag(neg=False, ci=False, pattern="A%", index=1):
    """CASE WHEN s LIKE <pattern> THEN 1 ELSE 0 END over column `index`,
    bound by hand; every variant PRINTED alike before Like's repr named
    `negated` and `case_insensitive`."""
    from igloo_tpu.plan.expr import Case, Like, Literal
    like = _bound(Like(_bound(Column("s", index=index), T.STRING), pattern,
                       negated=neg, case_insensitive=ci), T.BOOL)
    return _bound(Case([(like, _bound(Literal(1, T.INT64), T.INT64))],
                       _bound(Literal(0, T.INT64), T.INT64)), T.INT64)


_WORDS = ["Apple", "apricot", "Banana", "avocado", "Axe"]


def _words_table(n=400, seed=1):
    rng = np.random.default_rng(seed)
    return pa.table({"k": rng.choice(["x", "y", "z"], n).tolist(),
                     "s": rng.choice(_WORDS, n).tolist(),
                     "s2": rng.choice(_WORDS, n).tolist()})


@pytest.mark.parametrize("other", [dict(neg=True), dict(ci=True),
                                   dict(neg=True, ci=True), dict(index=2)],
                         ids=["not_like", "ilike", "not_ilike", "other_column"])
def test_direct_aggregate_keeps_arguments_that_print_alike_apart(other):
    """SUM(CASE WHEN s LIKE 'A%' ...) beside the same CASE over NOT LIKE,
    ILIKE, or a column of another index under the same name, under one
    small-domain key: each keeps a lane of its own (its sum differs from the
    first's) and equals the sort path. The LIKE handed over twice does not
    share either, since ISSUE 34: the CASE's `1` and `0` are arguments of
    the program, whose key holds no value, so its next execution may bring
    other ones (an argument that binds no literal still shares: q1's AVGs,
    tests/test_tpu_compile.py)."""
    import copy
    from igloo_tpu.exec.aggregate import seg_dims_for
    from igloo_tpu.plan.expr import fingerprint
    from igloo_tpu.utils import tracing
    b = from_arrow(_words_table())
    comp = ExprCompiler.for_batch(b)
    exprs = [_like_flag(), _like_flag(**other), copy.deepcopy(_like_flag())]
    assert fingerprint(exprs[0]) == fingerprint(exprs[2])
    assert fingerprint(exprs[0]) != fingerprint(exprs[1])
    aggs = [AggSpec(AggFunc.SUM, comp.compile(e), T.INT64, None)
            for e in exprs]
    g = [col(b, 0)]
    schema = out_schema_for(g, aggs, b, ["k", "a", "b", "a2"])
    consts = comp.pool.device_args()  # the LIKE lookup tables
    with tracing.counter_delta() as d:
        direct = aggregate_batch(b, g, aggs, schema, consts,
                                 seg_dims=seg_dims_for(g))
    # the live count, and a sum and a non-NULL count (a CASE computes a null
    # lane of its own) for each of the THREE arguments
    assert [a.arg.literals for a in aggs] == [2, 2, 2]
    assert d.get("agg.onepass_lanes") == 1 + 3 * 2
    got = _rows(direct)
    assert got == _rows(aggregate_batch(b, g, aggs, schema, consts))
    assert all(r[1] == r[3] for r in got)
    assert any(r[1] != r[2] for r in got)


@pytest.mark.parametrize("route", ["fused", "staged"])
def test_like_variants_in_one_group_by_through_sql(route):
    """The reviewer's query: LIKE, NOT LIKE and ILIKE arguments of one
    small-domain GROUP BY, through both compilers, against plain Python."""
    from igloo_tpu.engine import QueryEngine
    from igloo_tpu.exec.executor import Executor
    from igloo_tpu.utils import tracing
    t = _words_table()
    e = QueryEngine()
    e.register_table("t", t)
    sql = ("SELECT k, SUM(CASE WHEN s LIKE 'A%' THEN 1 ELSE 0 END) AS a, "
           "SUM(CASE WHEN s NOT LIKE 'A%' THEN 1 ELSE 0 END) AS b, "
           "SUM(CASE WHEN s ILIKE 'a%' THEN 1 ELSE 0 END) AS c, "
           "SUM(CASE WHEN s LIKE 'A%' THEN 1 ELSE 0 END) AS a2, "
           "COUNT(*) AS n FROM t GROUP BY k ORDER BY k")
    with tracing.counter_delta() as d:
        if route == "fused":
            got = e.execute(sql).to_pydict()
        else:
            ex = Executor(e._jit_cache, batch_cache=e.batch_cache)
            got = ex._staged_to_arrow(e.plan(sql)).to_pydict()
    # the live count; a sum and a non-NULL count for `a` with `a2`, `b`, `c`
    assert d.get("agg.onepass_lanes") == 1 + 3 * 2
    rows = list(zip(t["k"].to_pylist(), t["s"].to_pylist()))
    for i, k in enumerate(got["k"]):
        mine = [w for kk, w in rows if kk == k]
        a = sum(w.startswith("A") for w in mine)
        assert (got["a"][i], got["a2"][i]) == (a, a)
        assert got["b"][i] == len(mine) - a
        assert got["c"][i] == sum(w.lower().startswith("a") for w in mine)
        assert got["n"][i] == len(mine)


@pytest.mark.parametrize("second,want", [
    ("s NOT LIKE 'A%'", lambda w: not w.startswith("A")),
    ("s ILIKE 'A%'", lambda w: w.lower().startswith("a"))])
def test_program_key_tells_like_from_not_like(second, want):
    """One engine, `WHERE s LIKE 'A%'` and then its variant: before Like's
    repr named every field the two shared a program key, and the second
    query ran the first's program (172 where 228 rows match)."""
    from igloo_tpu.engine import QueryEngine
    t = _words_table()
    e = QueryEngine()
    e.register_table("t", t)
    words = t["s"].to_pylist()
    first = e.execute("SELECT COUNT(*) AS n FROM t WHERE s LIKE 'A%'")
    assert first.to_pydict()["n"] == [sum(w.startswith("A") for w in words)]
    got = e.execute(f"SELECT COUNT(*) AS n FROM t WHERE {second}")
    assert got.to_pydict()["n"] == [sum(map(want, words))]


def _fp_pairs():
    from igloo_tpu.plan.expr import (Cast, InList, IsNull, Like, Literal,
                                     ScalarSubquery)
    s = lambda i=0: _bound(Column("s", index=i), T.STRING)  # noqa: E731
    return {
        "column_index": (s(0), s(1)),
        "literal_type": (Literal(9000, T.INT32), Literal(9000, T.DATE32)),
        "literal_int_float_bool": (Literal(1), Literal(1.0)),
        "literal_bool_int": (Literal(True), Literal(1)),
        "literal_signed_zero": (Literal(0.0), Literal(-0.0)),
        "bound_dtype": (_bound(Literal(1), T.INT32), _bound(Literal(1), T.INT64)),
        "like_negated": (Like(s(), "A%"), Like(s(), "A%", negated=True)),
        "like_ci": (Like(s(), "A%"), Like(s(), "A%", case_insensitive=True)),
        "isnull_negated": (IsNull(s()), IsNull(s(), negated=True)),
        "inlist_negated": (InList(s(), [Literal("a")]),
                           InList(s(), [Literal("a")], negated=True)),
        "cast_target": (Cast(s(), T.INT32), Cast(s(), T.INT64)),
        "subquery_identity": (ScalarSubquery(object()),
                              ScalarSubquery(object())),
    }


@pytest.mark.parametrize("case", sorted(_fp_pairs()))
def test_fingerprint_reads_every_field(case):
    """Pairs that differ in one field — several of them PRINT alike, which
    is why a repr cannot say that two arguments share a lane — never share a
    fingerprint; a deep copy always does."""
    import copy
    from igloo_tpu.plan.expr import fingerprint
    a, b = _fp_pairs()[case]
    assert fingerprint(a) != fingerprint(b)
    if case != "subquery_identity":  # a subquery's AST equals nothing
        assert fingerprint(a) == fingerprint(copy.deepcopy(a))
    assert isinstance(hash(fingerprint(a)), int)  # a dict key


@pytest.mark.parametrize("route", ["fused", "staged"])
def test_q1_sf001_equals_pandas_on_both_routes(route):
    """TPC-H q1 at SF0.01 through the fused program and through the staged
    executor: the direct one-pass aggregate against the pandas oracle."""
    import datetime as dt
    from igloo_tpu.bench.tpch import QUERIES, gen_tables, register_all
    from igloo_tpu.engine import QueryEngine
    from igloo_tpu.exec.executor import Executor
    from igloo_tpu.utils import tracing
    tables = gen_tables(sf=0.01, seed=11)
    e = QueryEngine()
    register_all(e, tables)
    with tracing.counter_delta() as d:
        if route == "fused":
            got = e.execute(QUERIES["q1"]).to_pandas()
            assert d.get("fused.execute") >= 1
        else:
            ex = Executor(e._jit_cache, batch_cache=e.batch_cache)
            got = ex._staged_to_arrow(e.plan(QUERIES["q1"])).to_pandas()
    # q1's five distinct float64 sum lanes and ONE count lane, 6 of 16 ids
    assert d.get("agg.onepass_segments") == 6
    assert d.get("agg.onepass_lanes") == 6
    li = tables["lineitem"].to_pandas()
    f = li[li.l_shipdate <= dt.date(1998, 12, 1) - dt.timedelta(days=90)]
    f = f.assign(dp=f.l_extendedprice * (1 - f.l_discount))
    f = f.assign(ch=f.dp * (1 + f.l_tax))
    want = f.groupby(["l_returnflag", "l_linestatus"]).agg(
        sum_qty=("l_quantity", "sum"), sum_base_price=("l_extendedprice", "sum"),
        sum_disc_price=("dp", "sum"), sum_charge=("ch", "sum"),
        avg_qty=("l_quantity", "mean"), avg_price=("l_extendedprice", "mean"),
        avg_disc=("l_discount", "mean"), count_order=("l_quantity", "size"),
    ).reset_index().sort_values(["l_returnflag", "l_linestatus"])
    assert got["l_returnflag"].tolist() == want["l_returnflag"].tolist()
    assert got["l_linestatus"].tolist() == want["l_linestatus"].tolist()
    assert got["count_order"].tolist() == want["count_order"].tolist()
    for c in ("sum_qty", "sum_base_price", "sum_disc_price", "sum_charge",
              "avg_qty", "avg_price", "avg_disc"):
        np.testing.assert_allclose(got[c], want[c], rtol=1e-9)
