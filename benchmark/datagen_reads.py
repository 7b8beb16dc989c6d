"""`datagen.py`'s tables, cut to the columns some traffic mix reads.

For a configuration at a scale where the columns no query reads are most of
staging: at SF10 `lineitem` has 60 M rows and sixteen columns, of which
`scan_agg` reads seven, and its comments, modes and keys were built, written
as Parquet and never opened. The rows are `datagen.py`'s own, value for
value (benchmark/tests/test_sf10_cell.py holds every column equal): each
table draws from the same generator, seeded from (seed, table), in the same
order; what is left out is the draws after the last column wanted and the
Arrow arrays nobody asked for.

The columns kept are the union of `reads` over every file in traffic/, so a
cell stages the same bytes whichever of them it runs, and a later traffic
mix brings its columns with its file.
"""
from __future__ import annotations

import glob
import json
import os

import numpy as np
import pyarrow as pa

import datagen

HERE = os.path.dirname(os.path.abspath(__file__))
TABLES = datagen.TABLES


def read_columns() -> dict:
    """{table: the columns any traffic mix reads}."""
    cols: dict = {}
    for path in sorted(glob.glob(os.path.join(HERE, "traffic", "*.json"))):
        with open(path) as f:
            for q in json.load(f)["queries"]:
                for table, c in q["reads"].items():
                    cols.setdefault(table, set()).update(c)
    return cols


def _lineitem(rng, n, o_date, want: set):
    """datagen._lineitem as far as `want` needs it. -> Arrow table, or None
    where a column is wanted that is drawn after `l_receiptdate`'s: then the
    whole generator has to run."""
    late = {"l_shipinstruct", "l_shipmode", "l_comment"}
    if want & late:
        return None
    n_ord, n_part, n_supp = n["orders"], n["part"], n["supplier"]
    lines_per = rng.permutation(np.arange(n_ord) % 7 + 1)
    n_li = int(lines_per.sum())
    li_odate = np.repeat(o_date, lines_per)
    qty = rng.integers(1, 51, n_li).astype(np.float64)
    partkey = rng.integers(1, n_part + 1, n_li)
    discount = rng.integers(0, 11, n_li).astype(np.float64) / 100.0
    tax = rng.integers(0, 9, n_li).astype(np.float64) / 100.0
    ship = li_odate + rng.integers(1, 122, n_li)
    commit = li_odate + rng.integers(30, 91, n_li)
    receipt = ship + rng.integers(1, 31, n_li)
    cut = datagen._days(1995, 6, 17)
    returnflag = np.where(receipt <= cut, rng.integers(0, 2, n_li), 2)

    def linenumber():
        first = np.repeat(np.cumsum(lines_per) - lines_per, lines_per)
        return np.arange(n_li) - first + 1

    def date(days):
        return pa.array(days.astype("int32"), type=pa.int32()).cast(
            pa.date32())

    def int64(values):
        return pa.array(values, type=pa.int64())
    # in datagen's column order, each built only if it is wanted
    columns = {
        "l_orderkey": lambda: int64(
            np.repeat(np.arange(1, n_ord + 1), lines_per)),
        "l_partkey": lambda: int64(partkey),
        "l_suppkey": lambda: int64(
            ((partkey + linenumber() % 4 * (n_supp // 4 + 1)) % n_supp) + 1),
        "l_linenumber": lambda: int64(linenumber()),
        "l_quantity": lambda: qty,
        "l_extendedprice": lambda: np.round(
            qty * (900.0 + (partkey % 1000) * 1.1), 2),
        "l_discount": lambda: discount,
        "l_tax": lambda: tax,
        "l_returnflag": lambda: datagen._take(["R", "A", "N"], returnflag),
        "l_linestatus": lambda: datagen._take(
            ["F", "O"], (ship > cut).astype(np.int64)),
        "l_shipdate": lambda: date(ship),
        "l_commitdate": lambda: date(commit),
        "l_receiptdate": lambda: date(receipt),
    }
    return pa.table({name: make() for name, make in columns.items()
                     if name in want})


def gen_tables(sf: float, seed: int, tables=TABLES) -> dict:
    """{name: Arrow table} as datagen.gen_tables gives it, each table cut to
    the columns a traffic mix reads (a table none reads: whole)."""
    n = datagen._counts(sf)
    reads = read_columns()
    out = {}
    for name in tables:
        want = reads.get(name)
        if name == "lineitem" and want:
            out[name] = _lineitem(datagen._rng(seed, name), n,
                                  datagen._order_dates(seed, n["orders"]),
                                  want)
            if out[name] is not None:
                continue
        whole = datagen.gen_tables(sf=sf, seed=seed, tables=[name])[name]
        out[name] = whole.select([c for c in whole.column_names
                                  if c in want]) if want else whole
    return out
