"""The plain reference for TPC-H Q16 as the specification words it (clause
2.4.16, validation parameters): pandas, independent of the engine.

`tpch_pandas.q16` answers the repo's own variant of the query (`%pending%`,
no NOT LIKE, LIMIT 20) and stays as it is; this one answers
`queries/q16.sql`. NOT IN is written out with SQL's three-valued rule: a
row is kept only where the comparison with every key of the subquery is
known to be false, so a NULL among the keys keeps no row, and a NULL
`ps_suppkey` is kept only where the subquery has no keys at all.
"""
from __future__ import annotations

import pandas as pd

BRAND = "Brand#45"
TYPE_PREFIX = "MEDIUM POLISHED"
SIZES = [49, 14, 23, 45, 19, 3, 36, 9]


def not_in(values: pd.Series, keys: pd.Series) -> pd.Series:
    """SQL `values NOT IN (keys)` where it is TRUE (FALSE and UNKNOWN both
    drop the row)."""
    if len(keys) == 0:
        return pd.Series(True, index=values.index)
    if keys.isna().any():
        return pd.Series(False, index=values.index)
    return values.notna() & ~values.isin(keys)


def q16(t):
    ps, p, s = t["partsupp"], t["part"], t["supplier"]
    complaints = s[s.s_comment.str.contains("Customer.*Complaints",
                                            regex=True, na=False)].s_suppkey
    part = p[p.p_brand.notna() & (p.p_brand != BRAND)
             & ~p.p_type.str.startswith(TYPE_PREFIX, na=True)
             & p.p_size.isin(SIZES)]
    j = ps.merge(part[["p_partkey", "p_brand", "p_type", "p_size"]],
                 left_on="ps_partkey", right_on="p_partkey")
    j = j[not_in(j.ps_suppkey, complaints)]
    out = j.groupby(["p_brand", "p_type", "p_size"]).ps_suppkey.nunique() \
        .reset_index(name="supplier_cnt")
    return out.sort_values(
        ["supplier_cnt", "p_brand", "p_type", "p_size"],
        ascending=[False, True, True, True], kind="stable") \
        .reset_index(drop=True)
