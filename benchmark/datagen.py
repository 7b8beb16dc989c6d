"""TPC-H tables from a seed, as Arrow tables (the benchmark's own copy).

Copied from igloo_tpu/bench/tpch.py:gen_tables (PR 27) so that no later PR
can move the data the yardstick runs on. A vectorised numpy dbgen-alike:
the spec's 8 tables at their column widths, uniform keys, dates
1992-01-01..1998-12-01, discount/tax ranges, comments from a small word
pool. Not dbgen's bytes (configs list that under `assumed`).

Changes from the original: every table draws from a generator of its own,
seeded from (seed, table), so that a cell stages only the tables its queries
read and still gets the same rows for them (`lineitem` follows `orders`'
dates, which have a generator of their own); repeated strings are built as
Arrow dictionary takes, not Python lists; and every seed makes tables of the
same sizes (lines per order are a fixed multiset, permuted).
"""
from __future__ import annotations

import datetime as _dt

import numpy as np
import pyarrow as pa

_EPOCH = _dt.date(1970, 1, 1)


def _days(y, m, d):
    return (_dt.date(y, m, d) - _EPOCH).days


_START = _days(1992, 1, 1)
_END = _days(1998, 12, 1)

_NATIONS = [
    ("ALGERIA", 0), ("ARGENTINA", 1), ("BRAZIL", 1), ("CANADA", 1),
    ("EGYPT", 4), ("ETHIOPIA", 0), ("FRANCE", 3), ("GERMANY", 3),
    ("INDIA", 2), ("INDONESIA", 2), ("IRAN", 4), ("IRAQ", 4),
    ("JAPAN", 2), ("JORDAN", 4), ("KENYA", 0), ("MOROCCO", 0),
    ("MOZAMBIQUE", 0), ("PERU", 1), ("CHINA", 2), ("ROMANIA", 3),
    ("SAUDI ARABIA", 4), ("VIETNAM", 2), ("RUSSIA", 3),
    ("UNITED KINGDOM", 3), ("UNITED STATES", 1),
]
_REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
_SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "HOUSEHOLD", "MACHINERY"]
_PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
_SHIPMODES = ["AIR", "FOB", "MAIL", "RAIL", "REG AIR", "SHIP", "TRUCK"]
_INSTRUCTIONS = ["COLLECT COD", "DELIVER IN PERSON", "NONE",
                 "TAKE BACK RETURN"]
_TYPES_P1 = ["ECONOMY", "LARGE", "MEDIUM", "PROMO", "SMALL", "STANDARD"]
_TYPES_P2 = ["ANODIZED", "BRUSHED", "BURNISHED", "PLATED", "POLISHED"]
_TYPES_P3 = ["BRASS", "COPPER", "NICKEL", "STEEL", "TIN"]
_CONTAINERS_P1 = ["JUMBO", "LG", "MED", "SM", "WRAP"]
_CONTAINERS_P2 = ["BAG", "BOX", "CAN", "CASE", "DRUM", "JAR", "PACK", "PKG"]
_WORDS = ("the quick final pending special express regular furious ironic "
          "bold even silent slow careful deposits requests accounts foxes "
          "packages theodolites instructions pinto beans "
          "green forest lavender misty").split()


def _comments(rng, n, lo=2, hi=6):
    """Random word-pool comments. Above _POOL_N rows, sample from a pregenerated
    pool instead of building n python strings — vectorized path for SF >= 1
    (60M-row lineitem at SF10 would spend minutes in a python join loop). The
    pool preserves the LIKE-able patterns (q13 '%special%requests%', q16
    '%pending%', q9 '%green%') because it draws from the same word pool."""
    if n > _POOL_N:
        return _take(_comments_exact(rng, _POOL_N, lo, hi),
                     rng.integers(0, _POOL_N, n))
    return _comments_exact(rng, n, lo, hi)


_POOL_N = 50_000


def _comments_exact(rng, n, lo, hi):
    k = rng.integers(lo, hi + 1, n)
    idx = rng.integers(0, len(_WORDS), (n, hi))
    return [" ".join(_WORDS[idx[i, j]] for j in range(k[i])) for i in range(n)]


def _fmt(pattern: str, arr: np.ndarray) -> np.ndarray:
    """Vectorized sprintf over an int array (np.char.mod; no python loop)."""
    return np.char.mod(pattern, arr)


def _pick(choices: list, rng, n) -> np.ndarray:
    return np.asarray(choices, dtype=object)[rng.integers(0, len(choices), n)]


def _take(choices, idx: np.ndarray) -> pa.Array:
    """choices[idx] as an Arrow string array, without n Python strings."""
    return pa.array(list(choices), type=pa.string()).take(pa.array(idx))


def _phones(rng, nation: np.ndarray) -> list:
    n = len(nation)
    return np.char.add(np.char.add(np.char.add(
        _fmt("%d-", nation + 10), _fmt("%d-", rng.integers(100, 999, n))),
        _fmt("%d-", rng.integers(100, 999, n))),
        _fmt("%d", rng.integers(1000, 9999, n))).tolist()


def _money(rng, n, lo, hi):
    # decimal(15,2): generate in cents, expose as float64 (engine computes f64)
    cents = rng.integers(int(lo * 100), int(hi * 100) + 1, n)
    return cents.astype(np.float64) / 100.0


TABLES = ("region", "nation", "supplier", "part", "partsupp", "customer",
          "orders", "lineitem")
_N_NATION = len(_NATIONS)


def _counts(sf: float) -> dict:
    return {"supplier": max(int(10_000 * sf), 10),
            "part": max(int(200_000 * sf), 20),
            "customer": max(int(150_000 * sf), 15),
            "orders": max(int(1_500_000 * sf), 150)}


def _region(rng, n):
    return pa.table({
        "r_regionkey": pa.array(np.arange(5), type=pa.int64()),
        "r_name": _REGIONS,
        "r_comment": _comments(rng, 5),
    })


def _nation(rng, n):
    return pa.table({
        "n_nationkey": pa.array(np.arange(_N_NATION), type=pa.int64()),
        "n_name": [name for name, _ in _NATIONS],
        "n_regionkey": pa.array([r for _, r in _NATIONS], type=pa.int64()),
        "n_comment": _comments(rng, _N_NATION),
    })


def _supplier(rng, n):
    n_supp = n["supplier"]
    s_nation = rng.integers(0, _N_NATION, n_supp)
    return pa.table({
        "s_suppkey": pa.array(np.arange(1, n_supp + 1), type=pa.int64()),
        "s_name": _fmt("Supplier#%09d", np.arange(1, n_supp + 1)).tolist(),
        "s_address": _comments(rng, n_supp, 1, 3),
        "s_nationkey": pa.array(s_nation, type=pa.int64()),
        "s_phone": _phones(rng, s_nation),
        "s_acctbal": _money(rng, n_supp, -999.99, 9999.99),
        "s_comment": _comments(rng, n_supp),
    })


def _part(rng, n):
    n_part = n["part"]
    p_types = np.char.add(np.char.add(
        np.char.add(_pick(_TYPES_P1, rng, n_part).astype(str), " "),
        np.char.add(_pick(_TYPES_P2, rng, n_part).astype(str), " ")),
        _pick(_TYPES_P3, rng, n_part).astype(str)).tolist()
    return pa.table({
        "p_partkey": pa.array(np.arange(1, n_part + 1), type=pa.int64()),
        "p_name": np.char.add(np.char.add(
            np.char.add(_pick(_WORDS, rng, n_part).astype(str), " "),
            np.char.add(_pick(_WORDS, rng, n_part).astype(str), " ")),
            _pick(_WORDS, rng, n_part).astype(str)).tolist(),
        "p_mfgr": _fmt("Manufacturer#%d", rng.integers(1, 6, n_part)).tolist(),
        "p_brand": np.char.add(_fmt("Brand#%d", rng.integers(1, 6, n_part)),
                               _fmt("%d", rng.integers(1, 6, n_part))).tolist(),
        "p_type": p_types,
        "p_size": pa.array(rng.integers(1, 51, n_part), type=pa.int64()),
        "p_container": np.char.add(
            np.char.add(_pick(_CONTAINERS_P1, rng, n_part).astype(str), " "),
            _pick(_CONTAINERS_P2, rng, n_part).astype(str)).tolist(),
        "p_retailprice": _money(rng, n_part, 900.0, 2000.0),
        "p_comment": _comments(rng, n_part, 1, 3),
    })


def _partsupp(rng, n):
    n_part, n_supp = n["part"], n["supplier"]
    n_ps = n_part * 4
    ps_part = np.repeat(np.arange(1, n_part + 1), 4)
    ps_supp = ((ps_part + np.tile(np.arange(4), n_part) *
                (n_supp // 4 + 1)) % n_supp) + 1
    return pa.table({
        "ps_partkey": pa.array(ps_part, type=pa.int64()),
        "ps_suppkey": pa.array(ps_supp, type=pa.int64()),
        "ps_availqty": pa.array(rng.integers(1, 10_000, n_ps), type=pa.int64()),
        "ps_supplycost": _money(rng, n_ps, 1.0, 1000.0),
        "ps_comment": _comments(rng, n_ps),
    })


def _customer(rng, n):
    n_cust = n["customer"]
    c_nation = rng.integers(0, _N_NATION, n_cust)
    return pa.table({
        "c_custkey": pa.array(np.arange(1, n_cust + 1), type=pa.int64()),
        "c_name": _fmt("Customer#%09d", np.arange(1, n_cust + 1)).tolist(),
        "c_address": _comments(rng, n_cust, 1, 3),
        "c_nationkey": pa.array(c_nation, type=pa.int64()),
        "c_phone": _phones(rng, c_nation),
        "c_acctbal": _money(rng, n_cust, -999.99, 9999.99),
        "c_mktsegment": _take(_SEGMENTS, rng.integers(0, len(_SEGMENTS), n_cust)),
        "c_comment": _comments(rng, n_cust),
    })


def _order_dates(seed: int, n_ord: int) -> np.ndarray:
    """o_orderdate, from a generator of its own: `orders` and `lineitem`
    (ship/commit/receipt dates follow the order's) both need it."""
    return _rng(seed, "o_orderdate").integers(_START, _END - 151, n_ord)


def _orders(rng, n, o_date):
    n_cust, n_ord = n["customer"], n["orders"]
    # dbgen rule: custkeys divisible by 3 never place orders (drives q13's
    # zero-order bucket and q22's NOT EXISTS branch)
    o_cust = rng.integers(1, n_cust + 1, n_ord)
    o_cust = np.where(o_cust % 3 == 0, np.maximum(o_cust - 1, 1), o_cust)
    return pa.table({
        "o_orderkey": pa.array(np.arange(1, n_ord + 1), type=pa.int64()),
        "o_custkey": pa.array(o_cust, type=pa.int64()),
        "o_orderstatus": _take(["F", "O", "P"], rng.integers(0, 3, n_ord)),
        "o_totalprice": _money(rng, n_ord, 800.0, 500_000.0),
        "o_orderdate": pa.array(o_date.astype("int32"), type=pa.int32()).cast(
            pa.date32()),
        "o_orderpriority": _take(_PRIORITIES,
                                 rng.integers(0, len(_PRIORITIES), n_ord)),
        "o_clerk": _take(_fmt("Clerk#%09d", np.arange(1, 1001)).tolist(),
                         rng.integers(0, 1000, n_ord)),
        "o_shippriority": pa.array(np.zeros(n_ord, dtype=np.int64)),
        "o_comment": _comments(rng, n_ord),
    })


def _lineitem(rng, n, o_date):
    n_ord, n_part, n_supp = n["orders"], n["part"], n["supplier"]
    # 1-7 lines per order, each count equally often: the same multiset for
    # every seed, in another order, so that every seed makes a `lineitem` of
    # the same size (5,999,995 rows at SF1) and no seed changes the sizes
    # the program allocates. (Drawn per order the size moved by +-0.05 %.)
    lines_per = rng.permutation(np.arange(n_ord) % 7 + 1)
    n_li = int(lines_per.sum())
    li_order = np.repeat(np.arange(1, n_ord + 1), lines_per)
    li_odate = np.repeat(o_date, lines_per)
    # 1..k within each order: position minus the order's first position
    first = np.repeat(np.cumsum(lines_per) - lines_per, lines_per)
    linenumber = np.arange(n_li) - first + 1
    qty = rng.integers(1, 51, n_li).astype(np.float64)
    partkey = rng.integers(1, n_part + 1, n_li)
    # extendedprice = qty * part retail-ish price
    base_price = 900.0 + (partkey % 1000) * 1.1
    extended = np.round(qty * base_price, 2)
    discount = rng.integers(0, 11, n_li).astype(np.float64) / 100.0
    tax = rng.integers(0, 9, n_li).astype(np.float64) / 100.0
    ship = li_odate + rng.integers(1, 122, n_li)
    commit = li_odate + rng.integers(30, 91, n_li)
    receipt = ship + rng.integers(1, 31, n_li)
    cut = _days(1995, 6, 17)
    # R or A before the cut, N after; O once shipped after the cut
    returnflag = np.where(receipt <= cut, rng.integers(0, 2, n_li), 2)
    linestatus = (ship > cut).astype(np.int64)
    # dbgen rule: a line's supplier is one of the FOUR partsupp suppliers of
    # its part (same formula as ps_supp above with k = linenumber % 4) — so
    # lineitem x partsupp on (partkey, suppkey) actually joins (q9/q17/q20)
    li_k = linenumber % 4

    def date(days):
        return pa.array(days.astype("int32"), type=pa.int32()).cast(
            pa.date32())
    return pa.table({
        "l_orderkey": pa.array(li_order, type=pa.int64()),
        "l_partkey": pa.array(partkey, type=pa.int64()),
        "l_suppkey": pa.array(
            ((partkey + li_k * (n_supp // 4 + 1)) % n_supp) + 1,
            type=pa.int64()),
        "l_linenumber": pa.array(linenumber, type=pa.int64()),
        "l_quantity": qty,
        "l_extendedprice": extended,
        "l_discount": discount,
        "l_tax": tax,
        "l_returnflag": _take(["R", "A", "N"], returnflag),
        "l_linestatus": _take(["F", "O"], linestatus),
        "l_shipdate": date(ship),
        "l_commitdate": date(commit),
        "l_receiptdate": date(receipt),
        "l_shipinstruct": _take(_INSTRUCTIONS,
                                rng.integers(0, len(_INSTRUCTIONS), n_li)),
        "l_shipmode": _take(_SHIPMODES, rng.integers(0, len(_SHIPMODES), n_li)),
        "l_comment": _comments(rng, n_li, 1, 3),
    })


_MAKERS = {"region": _region, "nation": _nation, "supplier": _supplier,
           "part": _part, "partsupp": _partsupp, "customer": _customer,
           "orders": _orders, "lineitem": _lineitem}


def _rng(seed: int, what: str):
    """A generator of `what`'s own: (seed, what) -> the same stream whatever
    else is generated. `--seed` may exceed 2**31; SeedSequence takes any
    non-negative integer."""
    return np.random.default_rng([int(seed), *what.encode()])


def gen_tables(sf: float, seed: int, tables=TABLES) -> dict:
    """{name: Arrow table} for `tables`, at scale factor `sf`, from `seed`.
    A table's rows do not depend on which other tables are asked for."""
    n = _counts(sf)
    out = {}
    for name in tables:
        rng = _rng(seed, name)
        if name in ("orders", "lineitem"):
            out[name] = _MAKERS[name](rng, n, _order_dates(seed, n["orders"]))
        else:
            out[name] = _MAKERS[name](rng, n)
    return out
