"""TPC-H Q1, Q3 and Q6 with their substitution parameters as arguments
(rev. 3, clauses 2.4.1.3, 2.4.3.3, 2.4.6.3): plain single-threaded pandas in
float64, the text of `tpch_pandas.py`'s q1 / q3 / q6 with each validation
value replaced by a parameter, and one entry point per variant a traffic
file names (`traffic/scan_agg_streams.json`: `q1_s00` ... `q6_s01`; clause
5.3: one parameter set per query stream).

Input frames use INT DAYS since the epoch for date columns, as
`tpch_pandas.py`'s do. Q6's discount band is written as two two-decimal
literals, as `queries/q6.sql` writes `0.05 AND 0.07` for DISCOUNT 0.06: the
reference compares with the literals the SQL text holds, not with
`discount - 0.01` computed in float64."""
from __future__ import annotations

import datetime as _dt

_EPOCH = _dt.date(1970, 1, 1)


def _days(y, m, d):
    return (_dt.date(y, m, d) - _EPOCH).days


def _rev(df):
    return df.l_extendedprice * (1 - df.l_discount)


def q1(t, delta):
    """DELTA in [60, 120]: shipped up to `delta` days before 1998-12-01."""
    li = t["lineitem"]
    d = li[li.l_shipdate <= _days(1998, 12, 1) - delta]
    return d.assign(
        disc_price=_rev(d),
        charge=_rev(d) * (1 + d.l_tax),
    ).groupby(["l_returnflag", "l_linestatus"], as_index=False).agg(
        sum_qty=("l_quantity", "sum"), sum_base_price=("l_extendedprice", "sum"),
        sum_disc_price=("disc_price", "sum"), sum_charge=("charge", "sum"),
        avg_qty=("l_quantity", "mean"), avg_price=("l_extendedprice", "mean"),
        avg_disc=("l_discount", "mean"), count_order=("l_quantity", "size"),
    ).sort_values(["l_returnflag", "l_linestatus"])


def q3(t, segment, date):
    """SEGMENT one of clause 4.2.2.13's five; DATE `(y, m, d)`, a day in
    [1995-03-01, 1995-03-31]."""
    cut = _days(*date)
    c, o, li = t["customer"], t["orders"], t["lineitem"]
    c = c[c.c_mktsegment == segment][["c_custkey"]]
    o = o[o.o_orderdate < cut][["o_orderkey", "o_custkey", "o_orderdate",
                                "o_shippriority"]]
    li = li[li.l_shipdate > cut][["l_orderkey", "l_extendedprice",
                                  "l_discount"]]
    j = li.merge(o, left_on="l_orderkey", right_on="o_orderkey").merge(
        c, left_on="o_custkey", right_on="c_custkey")
    j = j.assign(revenue=_rev(j))
    return j.groupby(["l_orderkey", "o_orderdate", "o_shippriority"],
                     as_index=False).revenue.sum().sort_values(
        ["revenue", "o_orderdate"], ascending=[False, True]).head(10)


def q6(t, year, discount, quantity):
    """DATE = 1 January of `year` in [1993, 1997]; DISCOUNT in [0.02, 0.09],
    the band's ends as the two-decimal literals the text holds; QUANTITY 24
    or 25."""
    lo, hi = _days(year, 1, 1), _days(year + 1, 1, 1)
    d_lo = float(f"{discount - 0.01:.2f}")
    d_hi = float(f"{discount + 0.01:.2f}")
    li = t["lineitem"]
    d = li[(li.l_shipdate >= lo) & (li.l_shipdate < hi)
           & (li.l_discount >= d_lo) & (li.l_discount <= d_hi)
           & (li.l_quantity < quantity)]
    return float((d.l_extendedprice * d.l_discount).sum())


# --- the variants traffic/scan_agg_streams.json names: `parameters` there
#     and the literals of queries/q*_s01.sql hold the same values ------------

def q1_s00(t):
    return q1(t, 90)


def q1_s01(t):
    return q1(t, 68)


def q6_s00(t):
    return q6(t, 1994, 0.06, 24)


def q6_s01(t):
    return q6(t, 1996, 0.03, 25)
