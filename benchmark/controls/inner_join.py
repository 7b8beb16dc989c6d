"""The control of `correct` for a query whose answer counts what an OUTER
join keeps: the plain reference with each LEFT join made INNER, put in the
program's place. TPC-H Q13 then loses the customers without a counted order
(its `c_count = 0` row, a third of the customers and more), so `correct`
has to come out false. Where a traffic holds a query without such a
variant here, the control refuses to run rather than pass it unaltered."""
import pandas as pd
import pyarrow as pa

from compare import frame


def q13_inner(t: dict) -> pd.DataFrame:
    """oracle/tpch_pandas.py q13 with `how="inner"`."""
    c, o = t["customer"], t["orders"]
    o2 = o[~o.o_comment.str.contains("special.*requests", regex=True)]
    j = c[["c_custkey"]].merge(o2[["o_custkey", "o_orderkey"]],
                               left_on="c_custkey", right_on="o_custkey",
                               how="inner")
    cc = j.groupby("c_custkey").o_orderkey.count().reset_index(name="c_count")
    return cc.groupby("c_count", as_index=False).size().rename(
        columns={"size": "custdist"}).sort_values(
        ["custdist", "c_count"], ascending=[False, False])


INNER = {"tpch_pandas:q13": q13_inner}


def answers(kept: dict, traffic: dict, answers_from) -> dict:
    """{query: Arrow table}, as a deployment would have returned them."""
    frames = {name: frame(tbl) for name, tbl in kept.items()}
    out = {}
    for q in traffic["queries"]:
        if q["oracle"] not in INNER:
            raise ValueError(f"no INNER variant of {q['oracle']}")
        ans = INNER[q["oracle"]](frames)
        out[q["name"]] = pa.Table.from_pandas(ans, preserve_index=False)
    return out
