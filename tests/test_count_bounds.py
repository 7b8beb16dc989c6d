"""A COUNT's result carries the bound its input's capacity gives it
(`exec/aggregate.py agg_out_bounds`): a group counts at most every lane of
the aggregate's input, so COUNT and COUNT(*) come out bounded by
(0, input capacity) in the fused compiler's node metadata and in the staged
executor's output, and every other aggregate output stays unbounded. A
GROUP BY or ORDER BY over such a count then chooses as it does for any
bounded integer key — one packed lane (`pack.agg`, `pack.sort`) or, where
the input is small, the direct scatter — and its answer is the lex chain's,
row for row: the lex answer is taken with the bound removed."""
import numpy as np
import pyarrow as pa
import pytest

from igloo_tpu.engine import QueryEngine
from igloo_tpu.exec import executor as executor_mod
from igloo_tpu.exec import fused as fused_mod
from igloo_tpu.exec.batch import round_capacity
from igloo_tpu.exec.executor import Executor
from igloo_tpu.exec.fused import FusedCompiler
from igloo_tpu.utils import tracing

# TPC-H q13's shape: a count per customer over a LEFT JOIN (customers with
# no order count 0), then the customers per count
CUSTDIST = """
    SELECT c_count, COUNT(*) AS custdist
    FROM (SELECT c_custkey, COUNT(o_orderkey) AS c_count
          FROM customer LEFT JOIN orders ON c_custkey = o_custkey
          GROUP BY c_custkey) AS c_orders
    GROUP BY c_count
    ORDER BY custdist DESC, c_count DESC"""

# two counts of one group, ordered by both (k last: a total order)
TWO_COUNTS = """
    SELECT k, COUNT(*) AS n, COUNT(v) AS nv FROM t
    GROUP BY k ORDER BY n DESC, nv, k"""


def custdist_tables(n_orders: int, n_customers: int = 1000, seed: int = 43):
    """Every third customer places no order (clause 4.2's rule)."""
    rng = np.random.default_rng(seed)
    keys = np.arange(1, n_customers + 1, dtype=np.int64)
    placing = keys[keys % 3 != 0]
    return {
        "customer": pa.table({"c_custkey": pa.array(keys)}),
        "orders": pa.table({
            "o_orderkey": pa.array(np.arange(n_orders, dtype=np.int64) * 4),
            "o_custkey": pa.array(rng.choice(placing, n_orders))}),
    }


def two_count_table(n: int = 3000, seed: int = 44):
    rng = np.random.default_rng(seed)
    v = rng.integers(0, 100, n)
    return {"t": pa.table({
        "k": pa.array(rng.integers(0, 60, n)),
        "v": pa.array(v, mask=rng.random(n) < 0.3)})}


def engine(tables: dict) -> QueryEngine:
    eng = QueryEngine()
    for name, tbl in tables.items():
        eng.register_table(name, tbl)
    return eng


def run(tables: dict, sql: str, executor: str):
    """(answer, counter delta) of `sql` on a fresh engine, by the fused
    program or the staged executor."""
    eng = engine(tables)
    with tracing.counter_delta() as d:
        if executor == "fused":
            got = eng.execute(sql)
        else:
            ex = Executor(eng._jit_cache, batch_cache=eng.batch_cache)
            got = ex._staged_to_arrow(eng.plan(sql))
    if executor == "fused":
        # one program, whose answer stands (no fall to the staged executor)
        assert d.get("fused.execute") == 1 and not d.get("fused.unsupported")
        assert not d.get("join.direct_dup_fallback")
    return got, d


@pytest.fixture
def unbounded(monkeypatch):
    """Remove the bound from both executors: the lex chain's answer."""
    def remove():
        none = lambda aggs, cap: [None] * len(aggs)  # noqa: E731
        monkeypatch.setattr(fused_mod, "agg_out_bounds", none)
        monkeypatch.setattr(executor_mod, "agg_out_bounds", none)
    return remove


SELECTS = {
    "grouped": "SELECT k, COUNT(*) AS n, COUNT(v) AS nv, SUM(v) AS s, "
               "AVG(v) AS a, MIN(v) AS lo, MAX(v) AS hi FROM t GROUP BY k",
    "global": "SELECT COUNT(*) AS n, COUNT(v) AS nv, SUM(v) AS s, "
              "AVG(v) AS a, MIN(v) AS lo, MAX(v) AS hi FROM t",
}


@pytest.mark.parametrize("shape", sorted(SELECTS))
def test_counts_carry_the_input_capacity(shape):
    tables = two_count_table()
    cap = round_capacity(tables["t"].num_rows)
    want = [(0, cap), (0, cap), None, None, None, None]
    eng = engine(tables)
    eng.execute(SELECTS[shape])            # loads the scan
    plan = eng.plan(SELECTS[shape])
    _run, _key, meta = FusedCompiler(eng._executor()).compile(plan)
    staged = Executor(eng._jit_cache, batch_cache=eng.batch_cache)._exec(plan)
    n_keys = 1 if shape == "grouped" else 0
    assert meta.bounds[n_keys:] == want
    assert [c.bounds for c in staged.columns][n_keys:] == want
    if n_keys:
        # the group key keeps the scan's bound in the fused metadata
        assert meta.bounds[0] == (0, 59)


@pytest.mark.parametrize("executor", ["fused", "staged"])
def test_group_by_a_count_packs_and_equals_the_lex_chain(executor,
                                                         unbounded):
    """Over 70,000 orders the join's output is wider than 2^16 lanes and
    than twice the count per customer's output: seg_dims_for declines the
    count of counts, which sorts ONE packed lane."""
    tables = custdist_tables(n_orders=70_000)
    got, d = run(tables, CUSTDIST, executor)
    assert d.get("pack.agg") == 1 and d.get("pack.sort") == 1
    assert d.get("agg.direct_scatter") == 1          # the count per customer
    rows = dict(zip(got.column("c_count").to_pylist(),
                    got.column("custdist").to_pylist()))
    assert rows[0] == 333                  # the customers without an order
    assert sum(rows.values()) == 1000
    unbounded()
    want, d0 = run(tables, CUSTDIST, executor)
    assert not d0.get("pack.agg") and not d0.get("pack.sort")
    assert got.to_pydict() == want.to_pydict()


@pytest.mark.parametrize("executor", ["fused", "staged"])
def test_a_count_over_a_small_input_scatters(executor, unbounded):
    """Over 1,000 orders of 200 customers the count's bound spans under
    2^16 segments: the count of counts is a direct scatter, as any small
    bounded key is."""
    tables = custdist_tables(n_orders=1000, n_customers=200)
    got, d = run(tables, CUSTDIST, executor)
    assert d.get("join.direct_routes") == 1
    assert d.get("agg.direct_scatter") == 2 and not d.get("pack.agg")
    unbounded()
    want, d0 = run(tables, CUSTDIST, executor)
    assert d0.get("agg.direct_scatter") == 1
    assert got.to_pydict() == want.to_pydict()


@pytest.mark.parametrize("executor", ["fused", "staged"])
def test_order_by_two_counts_packs_and_keeps_the_lex_order(executor,
                                                           unbounded):
    tables = two_count_table()
    got, d = run(tables, TWO_COUNTS, executor)
    assert d.get("pack.sort") == 1
    n, nv = got.column("n").to_pylist(), got.column("nv").to_pylist()
    assert n == sorted(n, reverse=True) and n != nv
    unbounded()
    want, d0 = run(tables, TWO_COUNTS, executor)
    assert not d0.get("pack.sort")
    assert got.to_pydict() == want.to_pydict()
