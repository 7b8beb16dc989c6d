"""Single-threaded pandas implementations of all 22 TPC-H queries.

These are the tests' and chip_smoke.py's reference — the stand-in for the
reference's working single-node CPU path (DataFusion via QueryEngine::execute,
/root/reference/crates/engine/src/lib.rs:54-57), which cannot be installed in
this environment (no package egress; see BASELINE.md). Idiomatic, reasonably
optimized pandas: vectorized masks, pre-projected merge inputs, no python row
loops.

Input frames use INT DAYS since epoch for date columns (callers convert once
up front)."""
from __future__ import annotations

import datetime as _dt

import numpy as np
import pandas as pd

_EPOCH = _dt.date(1970, 1, 1)


def _days(y, m, d):
    return (_dt.date(y, m, d) - _EPOCH).days


def _rev(df):
    return df.l_extendedprice * (1 - df.l_discount)


def _year(days_col):
    return pd.to_datetime(days_col, unit="D", origin="unix").dt.year


def q1(t):
    li = t["lineitem"]
    d = li[li.l_shipdate <= _days(1998, 12, 1) - 90]
    return d.assign(
        disc_price=_rev(d),
        charge=_rev(d) * (1 + d.l_tax),
    ).groupby(["l_returnflag", "l_linestatus"], as_index=False).agg(
        sum_qty=("l_quantity", "sum"), sum_base_price=("l_extendedprice", "sum"),
        sum_disc_price=("disc_price", "sum"), sum_charge=("charge", "sum"),
        avg_qty=("l_quantity", "mean"), avg_price=("l_extendedprice", "mean"),
        avg_disc=("l_discount", "mean"), count_order=("l_quantity", "size"),
    ).sort_values(["l_returnflag", "l_linestatus"])


def q2(t):
    p, s, ps, n, r = (t["part"], t["supplier"], t["partsupp"], t["nation"],
                      t["region"])
    eu = n.merge(r[r.r_name == "EUROPE"][["r_regionkey"]],
                 left_on="n_regionkey", right_on="r_regionkey")
    sj = s.merge(eu[["n_nationkey", "n_name"]], left_on="s_nationkey",
                 right_on="n_nationkey")
    sel = p[(p.p_size == 15) & p.p_type.str.endswith("BRASS")]
    j = (ps.merge(sj, left_on="ps_suppkey", right_on="s_suppkey")
         .merge(sel[["p_partkey", "p_mfgr"]], left_on="ps_partkey",
                right_on="p_partkey"))
    mins = j.groupby("p_partkey").ps_supplycost.transform("min")
    return j[j.ps_supplycost == mins][
        ["s_acctbal", "s_name", "n_name", "p_partkey", "p_mfgr", "s_address",
         "s_phone", "s_comment"]].sort_values(
        ["s_acctbal", "n_name", "s_name", "p_partkey"],
        ascending=[False, True, True, True]).head(100)


def q3(t):
    cut = _days(1995, 3, 15)
    c, o, li = t["customer"], t["orders"], t["lineitem"]
    c = c[c.c_mktsegment == "BUILDING"][["c_custkey"]]
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


def q4(t):
    o, li = t["orders"], t["lineitem"]
    f = o[(o.o_orderdate >= _days(1993, 7, 1)) &
          (o.o_orderdate < _days(1993, 10, 1))]
    late = li[li.l_commitdate < li.l_receiptdate].l_orderkey.unique()
    f = f[f.o_orderkey.isin(late)]
    return f.groupby("o_orderpriority", as_index=False).size().rename(
        columns={"size": "order_count"}).sort_values("o_orderpriority")


def q5(t):
    lo, hi = _days(1994, 1, 1), _days(1995, 1, 1)
    r, n, s, c = t["region"], t["nation"], t["supplier"], t["customer"]
    o, li = t["orders"], t["lineitem"]
    r = r[r.r_name == "ASIA"][["r_regionkey"]]
    n = n.merge(r, left_on="n_regionkey", right_on="r_regionkey")
    o = o[(o.o_orderdate >= lo) & (o.o_orderdate < hi)]
    j = (li.merge(o[["o_orderkey", "o_custkey"]], left_on="l_orderkey",
                  right_on="o_orderkey")
         .merge(s[["s_suppkey", "s_nationkey"]], left_on="l_suppkey",
                right_on="s_suppkey")
         .merge(c[["c_custkey", "c_nationkey"]], left_on="o_custkey",
                right_on="c_custkey"))
    j = j[j.c_nationkey == j.s_nationkey]
    j = j.merge(n[["n_nationkey", "n_name"]], left_on="s_nationkey",
                right_on="n_nationkey")
    j = j.assign(revenue=_rev(j))
    return j.groupby("n_name", as_index=False).revenue.sum().sort_values(
        "revenue", ascending=False)


def q6(t):
    lo, hi = _days(1994, 1, 1), _days(1995, 1, 1)
    li = t["lineitem"]
    d = li[(li.l_shipdate >= lo) & (li.l_shipdate < hi)
           & (li.l_discount >= 0.05) & (li.l_discount <= 0.07)
           & (li.l_quantity < 24)]
    return float((d.l_extendedprice * d.l_discount).sum())


def q7(t):
    li, o, c, s, n = (t["lineitem"], t["orders"], t["customer"],
                      t["supplier"], t["nation"])
    li = li[(li.l_shipdate >= _days(1995, 1, 1)) &
            (li.l_shipdate <= _days(1996, 12, 31))]
    fr_ge = n[n.n_name.isin(["FRANCE", "GERMANY"])]
    j = (li[["l_orderkey", "l_suppkey", "l_shipdate", "l_extendedprice",
             "l_discount"]]
         .merge(s[["s_suppkey", "s_nationkey"]], left_on="l_suppkey",
                right_on="s_suppkey")
         .merge(fr_ge[["n_nationkey", "n_name"]].rename(
             columns={"n_name": "supp_nation"}),
             left_on="s_nationkey", right_on="n_nationkey")
         .merge(o[["o_orderkey", "o_custkey"]], left_on="l_orderkey",
                right_on="o_orderkey")
         .merge(c[["c_custkey", "c_nationkey"]], left_on="o_custkey",
                right_on="c_custkey")
         .merge(fr_ge[["n_nationkey", "n_name"]].rename(
             columns={"n_name": "cust_nation"}),
             left_on="c_nationkey", right_on="n_nationkey",
             suffixes=("", "_c")))
    j = j[((j.supp_nation == "FRANCE") & (j.cust_nation == "GERMANY")) |
          ((j.supp_nation == "GERMANY") & (j.cust_nation == "FRANCE"))]
    j = j.assign(l_year=_year(j.l_shipdate), volume=_rev(j))
    return j.groupby(["supp_nation", "cust_nation", "l_year"],
                     as_index=False).volume.sum().sort_values(
        ["supp_nation", "cust_nation", "l_year"])


def q8(t):
    li, o, c, s, n, r, p = (t["lineitem"], t["orders"], t["customer"],
                            t["supplier"], t["nation"], t["region"], t["part"])
    o = o[(o.o_orderdate >= _days(1995, 1, 1)) &
          (o.o_orderdate <= _days(1996, 12, 31))]
    j = (li.merge(p[p.p_type == "ECONOMY ANODIZED STEEL"][["p_partkey"]],
                  left_on="l_partkey", right_on="p_partkey")
         .merge(s[["s_suppkey", "s_nationkey"]], left_on="l_suppkey",
                right_on="s_suppkey")
         .merge(o[["o_orderkey", "o_custkey", "o_orderdate"]],
                left_on="l_orderkey", right_on="o_orderkey")
         .merge(c[["c_custkey", "c_nationkey"]], left_on="o_custkey",
                right_on="c_custkey"))
    am = n.merge(r[r.r_name == "AMERICA"][["r_regionkey"]],
                 left_on="n_regionkey", right_on="r_regionkey")[["n_nationkey"]]
    j = j.merge(am, left_on="c_nationkey", right_on="n_nationkey")
    j = j.merge(n[["n_nationkey", "n_name"]], left_on="s_nationkey",
                right_on="n_nationkey", suffixes=("", "_s"))
    j = j.assign(o_year=_year(j.o_orderdate), volume=_rev(j))
    g = j.groupby("o_year").apply(
        lambda d: d[d.n_name == "BRAZIL"].volume.sum() / d.volume.sum()
        if len(d) else 0.0, include_groups=False)
    return g.reset_index(name="mkt_share").sort_values("o_year")


def q9(t):
    li, s, ps, o, n, p = (t["lineitem"], t["supplier"], t["partsupp"],
                          t["orders"], t["nation"], t["part"])
    j = (li.merge(p[p.p_name.str.contains("green")][["p_partkey"]],
                  left_on="l_partkey", right_on="p_partkey")
         .merge(s[["s_suppkey", "s_nationkey"]], left_on="l_suppkey",
                right_on="s_suppkey")
         .merge(ps[["ps_partkey", "ps_suppkey", "ps_supplycost"]],
                left_on=["l_partkey", "l_suppkey"],
                right_on=["ps_partkey", "ps_suppkey"])
         .merge(o[["o_orderkey", "o_orderdate"]], left_on="l_orderkey",
                right_on="o_orderkey")
         .merge(n[["n_nationkey", "n_name"]], left_on="s_nationkey",
                right_on="n_nationkey"))
    j = j.assign(o_year=_year(j.o_orderdate),
                 amount=_rev(j) - j.ps_supplycost * j.l_quantity)
    return j.groupby(["n_name", "o_year"], as_index=False).amount.sum() \
        .sort_values(["n_name", "o_year"], ascending=[True, False])


def q10(t):
    c, o, li, n = t["customer"], t["orders"], t["lineitem"], t["nation"]
    o = o[(o.o_orderdate >= _days(1993, 10, 1)) &
          (o.o_orderdate < _days(1994, 1, 1))]
    li = li[li.l_returnflag == "R"]
    j = (li[["l_orderkey", "l_extendedprice", "l_discount"]]
         .merge(o[["o_orderkey", "o_custkey"]], left_on="l_orderkey",
                right_on="o_orderkey")
         .merge(c, left_on="o_custkey", right_on="c_custkey")
         .merge(n[["n_nationkey", "n_name"]], left_on="c_nationkey",
                right_on="n_nationkey"))
    j = j.assign(revenue=_rev(j))
    return j.groupby(["c_custkey", "c_name", "c_acctbal", "c_phone", "n_name",
                      "c_address", "c_comment"], as_index=False) \
        .revenue.sum().sort_values("revenue", ascending=False).head(20)


def q11(t):
    ps, s, n = t["partsupp"], t["supplier"], t["nation"]
    de = s.merge(n[n.n_name == "GERMANY"][["n_nationkey"]],
                 left_on="s_nationkey", right_on="n_nationkey")[["s_suppkey"]]
    j = ps.merge(de, left_on="ps_suppkey", right_on="s_suppkey")
    j = j.assign(v=j.ps_supplycost * j.ps_availqty)
    g = j.groupby("ps_partkey", as_index=False).v.sum()
    return g[g.v > j.v.sum() * 0.0001].sort_values("v", ascending=False)


def q12(t):
    o, li = t["orders"], t["lineitem"]
    li = li[li.l_shipmode.isin(["MAIL", "SHIP"]) &
            (li.l_commitdate < li.l_receiptdate) &
            (li.l_shipdate < li.l_commitdate) &
            (li.l_receiptdate >= _days(1994, 1, 1)) &
            (li.l_receiptdate < _days(1995, 1, 1))]
    j = li[["l_orderkey", "l_shipmode"]].merge(
        o[["o_orderkey", "o_orderpriority"]], left_on="l_orderkey",
        right_on="o_orderkey")
    hi = j.o_orderpriority.isin(["1-URGENT", "2-HIGH"])
    return j.assign(h=hi.astype(int), l=(~hi).astype(int)).groupby(
        "l_shipmode", as_index=False).agg(high_line_count=("h", "sum"),
                                          low_line_count=("l", "sum")) \
        .sort_values("l_shipmode")


def q13(t):
    c, o = t["customer"], t["orders"]
    o2 = o[~o.o_comment.str.contains("special.*requests", regex=True)]
    j = c[["c_custkey"]].merge(o2[["o_custkey", "o_orderkey"]],
                               left_on="c_custkey", right_on="o_custkey",
                               how="left")
    cc = j.groupby("c_custkey").o_orderkey.count().reset_index(name="c_count")
    return cc.groupby("c_count", as_index=False).size().rename(
        columns={"size": "custdist"}).sort_values(
        ["custdist", "c_count"], ascending=[False, False])


def q14(t):
    li, p = t["lineitem"], t["part"]
    li = li[(li.l_shipdate >= _days(1995, 9, 1)) &
            (li.l_shipdate < _days(1995, 10, 1))]
    j = li.merge(p[["p_partkey", "p_type"]], left_on="l_partkey",
                 right_on="p_partkey")
    r = _rev(j)
    return float(100.0 * r[j.p_type.str.startswith("PROMO")].sum() / r.sum())


def q15(t):
    li, s = t["lineitem"], t["supplier"]
    d = li[(li.l_shipdate >= _days(1996, 1, 1)) &
           (li.l_shipdate < _days(1996, 4, 1))]
    rev = d.assign(r=_rev(d)).groupby("l_suppkey", as_index=False).r.sum()
    top = rev[rev.r == rev.r.max()]
    return s.merge(top, left_on="s_suppkey", right_on="l_suppkey")[
        ["s_suppkey", "s_name", "s_address", "s_phone", "r"]] \
        .sort_values("s_suppkey")


def q16(t):
    ps, p, s = t["partsupp"], t["part"], t["supplier"]
    bad = s[s.s_comment.str.contains("pending")].s_suppkey
    j = ps.merge(p[["p_partkey", "p_brand", "p_type", "p_size"]],
                 left_on="ps_partkey", right_on="p_partkey")
    j = j[(j.p_brand != "Brand#45") &
          j.p_size.isin([49, 14, 23, 45, 19, 3, 36, 9]) &
          ~j.ps_suppkey.isin(bad)]
    return j.groupby(["p_brand", "p_type", "p_size"]).ps_suppkey.nunique() \
        .reset_index(name="supplier_cnt").sort_values(
        ["supplier_cnt", "p_brand", "p_type", "p_size"],
        ascending=[False, True, True, True]).head(20)


def q17(t):
    li, p = t["lineitem"], t["part"]
    sel = p[(p.p_brand == "Brand#23") & (p.p_container == "MED BOX")]
    j = li.merge(sel[["p_partkey"]], left_on="l_partkey", right_on="p_partkey")
    avgq = li.groupby("l_partkey").l_quantity.mean()
    j = j[j.l_quantity < 0.2 * j.l_partkey.map(avgq)]
    return float(j.l_extendedprice.sum() / 7.0)


def q18(t):
    c, o, li = t["customer"], t["orders"], t["lineitem"]
    big = li.groupby("l_orderkey").l_quantity.sum()
    big = big[big > 150].index
    j = o[o.o_orderkey.isin(big)].merge(
        c[["c_custkey", "c_name"]], left_on="o_custkey", right_on="c_custkey")
    j = j.merge(li[["l_orderkey", "l_quantity"]], left_on="o_orderkey",
                right_on="l_orderkey")
    return j.groupby(["c_name", "c_custkey", "o_orderkey", "o_orderdate",
                      "o_totalprice"], as_index=False).l_quantity.sum() \
        .sort_values(["o_totalprice", "o_orderdate"],
                     ascending=[False, True]).head(100)


def q19(t):
    li, p = t["lineitem"], t["part"]
    li = li[li.l_shipmode.isin(["AIR", "REG AIR"])]
    j = li.merge(p[["p_partkey", "p_brand", "p_size"]], left_on="l_partkey",
                 right_on="p_partkey")
    m = (((j.p_brand == "Brand#12") & j.l_quantity.between(1, 11) &
          j.p_size.between(1, 5)) |
         ((j.p_brand == "Brand#23") & j.l_quantity.between(10, 20) &
          j.p_size.between(1, 10)) |
         ((j.p_brand == "Brand#34") & j.l_quantity.between(20, 30) &
          j.p_size.between(1, 15)))
    return float(_rev(j[m]).sum())


def q20(t):
    li, s, ps, p, n = (t["lineitem"], t["supplier"], t["partsupp"], t["part"],
                       t["nation"])
    fparts = p[p.p_name.str.startswith("forest")][["p_partkey"]]
    shipped = li[(li.l_shipdate >= _days(1994, 1, 1)) &
                 (li.l_shipdate < _days(1995, 1, 1))]
    qty = shipped.groupby(["l_partkey", "l_suppkey"], as_index=False) \
        .l_quantity.sum()
    cand = ps.merge(fparts, left_on="ps_partkey", right_on="p_partkey") \
        .merge(qty, left_on=["ps_partkey", "ps_suppkey"],
               right_on=["l_partkey", "l_suppkey"], how="inner")
    cand = cand[cand.ps_availqty > 0.5 * cand.l_quantity]
    ca = n[n.n_name == "CANADA"][["n_nationkey"]]
    sj = s.merge(ca, left_on="s_nationkey", right_on="n_nationkey")
    return sj[sj.s_suppkey.isin(set(cand.ps_suppkey))][
        ["s_name", "s_address"]].sort_values("s_name")


def q21(t):
    li, s, o, n = t["lineitem"], t["supplier"], t["orders"], t["nation"]
    sa = s.merge(n[n.n_name == "SAUDI ARABIA"][["n_nationkey"]],
                 left_on="s_nationkey", right_on="n_nationkey")
    l1 = li[li.l_receiptdate > li.l_commitdate]
    l1 = l1.merge(o[o.o_orderstatus == "F"][["o_orderkey"]],
                  left_on="l_orderkey", right_on="o_orderkey")
    l1 = l1.merge(sa[["s_suppkey", "s_name"]], left_on="l_suppkey",
                  right_on="s_suppkey")
    multi = li.groupby("l_orderkey").l_suppkey.nunique()
    late = li[li.l_receiptdate > li.l_commitdate] \
        .groupby("l_orderkey").l_suppkey.nunique()
    keep = (l1.l_orderkey.map(multi).fillna(1) > 1) & \
        (l1.l_orderkey.map(late).fillna(0) == 1)
    return l1[keep].groupby("s_name", as_index=False).size().rename(
        columns={"size": "numwait"}).sort_values(
        ["numwait", "s_name"], ascending=[False, True]).head(100)


def q22(t):
    c, o = t["customer"], t["orders"]
    codes = {"13", "31", "23", "29", "30", "18", "17"}
    cc = c.assign(code=c.c_phone.str[:2])
    pool = cc[cc.code.isin(codes)]
    avg = pool[pool.c_acctbal > 0].c_acctbal.mean()
    sel = pool[(pool.c_acctbal > avg) &
               ~pool.c_custkey.isin(set(o.o_custkey))]
    return sel.groupby("code", as_index=False).agg(
        numcust=("c_custkey", "size"), totacctbal=("c_acctbal", "sum")) \
        .sort_values("code")


PANDAS_QUERIES = {f"q{i}": globals()[f"q{i}"] for i in range(1, 23)}
