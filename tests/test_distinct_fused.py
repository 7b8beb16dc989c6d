"""DISTINCT aggregates run as the two grouped aggregates the optimizer splits
them into (`plan/aggsplit.py split_distinct`): stage 1 groups by the keys and
the distinct argument, stage 2 by the keys over stage 1's rows. The fused
compiler takes both into ONE program (no `fused.unsupported`), the staged
executor runs them as two aggregates, and each plan walk counts the split
once (`agg.distinct`). Both equal a pandas reference on seeded data with
NULLs in the argument and in the keys: NULL arguments are not counted, a
COUNT(DISTINCT) of an empty input reads 0 and SUM / AVG(DISTINCT) NULL, and
SUM / AVG(DISTINCT) add each value once. Two distinct arguments are still
refused, with the staged executor's error of before."""
import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

from igloo_tpu.engine import QueryEngine
from igloo_tpu.errors import NotSupportedError
from igloo_tpu.exec.executor import Executor
from igloo_tpu.utils import tracing

N = 700


def table() -> pa.Table:
    rng = np.random.default_rng(4600000201)
    x = rng.integers(0, 40, N)
    v = rng.integers(-50, 50, N) / 4.0
    g = rng.choice(["a", "b", "c"], N).astype(object)
    h = rng.integers(0, 4, N)
    return pa.table({
        "g": pa.array(g, mask=rng.random(N) < 0.1),
        "h": pa.array(h, mask=rng.random(N) < 0.1),
        "x": pa.array(x, mask=rng.random(N) < 0.15),
        "v": pa.array(v, mask=rng.random(N) < 0.1),
        "b": pa.array(np.where(np.arange(N) % 2, "p", "q")),
        "u": pa.array(rng.integers(0, 450, N)),
    })


def distinct_stats(s: pd.Series, name: str) -> dict:
    """COUNT, SUM and AVG(DISTINCT) of `s` as SQL has them."""
    vals = s.dropna().unique()
    return {f"c{name}": len(vals),
            f"s{name}": vals.sum() if len(vals) else np.nan,
            f"a{name}": vals.mean() if len(vals) else np.nan}


def grouped(df: pd.DataFrame, keys: list, fn) -> pd.DataFrame:
    rows = [dict(zip(keys, k if isinstance(k, tuple) else (k,)), **fn(part))
            for k, part in df.groupby(keys, dropna=False)]
    return pd.DataFrame(rows)


CASES = {
    # COUNT, SUM and AVG(DISTINCT), NULLs in the argument and in both keys
    "count_sum_avg": (
        "SELECT g, h, COUNT(DISTINCT x) AS cx, SUM(DISTINCT x) AS sx, "
        "AVG(DISTINCT x) AS ax FROM t GROUP BY g, h",
        lambda df: grouped(df, ["g", "h"],
                           lambda p: distinct_stats(p.x, "x"))),
    # beside plain aggregates, which merge their stage-1 partials
    "mixed_with_plain": (
        "SELECT g, COUNT(DISTINCT x) AS cx, COUNT(*) AS n, SUM(v) AS sv, "
        "MIN(v) AS mn, COUNT(v) AS nv FROM t GROUP BY g",
        lambda df: grouped(df, ["g"], lambda p: {
            "cx": p.x.nunique(), "n": len(p),
            "sv": p.v.sum() if p.v.notna().any() else np.nan,
            "mn": p.v.min(), "nv": int(p.v.notna().sum())})),
    # no GROUP BY: one row; a string argument
    "global": (
        "SELECT COUNT(DISTINCT g) AS cg, COUNT(*) AS n, SUM(x) AS sx, "
        "AVG(v) AS av FROM t",
        lambda df: pd.DataFrame([{"cg": df.g.nunique(), "n": len(df),
                                  "sx": df.x.sum(), "av": df.v.mean()}])),
    # an empty input still yields the global row: 0, NULL, NULL, 0
    "empty": (
        "SELECT COUNT(DISTINCT x) AS cx, SUM(DISTINCT x) AS sx, "
        "AVG(DISTINCT x) AS ax, COUNT(*) AS n FROM t WHERE u < 0",
        lambda df: pd.DataFrame([{"cx": 0, "sx": np.nan, "ax": np.nan,
                                  "n": 0}])),
    # hundreds of distinct values in each group
    "many_values": (
        "SELECT b, COUNT(DISTINCT u) AS cu, SUM(DISTINCT u) AS su FROM t "
        "GROUP BY b",
        lambda df: grouped(df, ["b"], lambda p: {
            "cu": p.u.nunique(), "su": p.u.unique().sum()})),
}


def canonical(df: pd.DataFrame) -> pd.DataFrame:
    """Rows in one order whatever the engine's (no ORDER BY above), NULL
    keys as a sentinel, every number as float."""
    keys = [c for c in df.columns
            if not pd.api.types.is_numeric_dtype(df[c])]
    out = df.copy()
    for c in out.columns:
        out[c] = out[c].astype(object).fillna("<null>").astype(str) \
            if c in keys else out[c].astype(float)
    return out.sort_values(list(out.columns)).reset_index(drop=True)


@pytest.mark.parametrize("executor", ["fused", "staged"])
@pytest.mark.parametrize("case", list(CASES))
def test_distinct_aggregates_equal_the_reference(executor, case):
    sql, expect = CASES[case]
    tbl = table()
    eng = QueryEngine()
    eng.register_table("t", tbl)
    with tracing.counter_delta() as d:
        if executor == "fused":
            got = eng.execute(sql)
        else:
            ex = Executor(eng._jit_cache, batch_cache=eng.batch_cache)
            got = ex._staged_to_arrow(eng.plan(sql))
    assert d.get("agg.distinct") == 1
    assert not d.get("fused.unsupported")
    assert bool(d.get("fused.execute")) is (executor == "fused")
    want = expect(tbl.to_pandas())[got.column_names]
    pd.testing.assert_frame_equal(canonical(got.to_pandas()), canonical(want),
                                  check_dtype=False, rtol=1e-12)


def test_two_distinct_arguments_are_refused():
    eng = QueryEngine()
    eng.register_table("t", table())
    with pytest.raises(NotSupportedError,
                       match="multiple distinct aggregate arguments"):
        eng.execute("SELECT COUNT(DISTINCT x) AS cx, COUNT(DISTINCT u) AS cu "
                    "FROM t")
