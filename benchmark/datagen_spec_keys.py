"""`datagen_reads.py`'s tables with the order keys TPC-H's own population
has: `O_ORDERKEY` is unique and SPARSE within [1, SF x 1,500,000 x 4]
(clause 4.2): of every 32 key values 8 are used, as dbgen's `mk_sparse`
writes them — the i-th order (i = 1..n) gets

    key(i) = 32 * (i // 8) + i % 8

so at SF10 the 15 M keys span 1..60,000,000, and `L_ORDERKEY` names its
order's key. `datagen.py` numbers orders 1..n densely, which a positional
join's table (sized by the key's range) reads four times smaller than the
spec's keys need. Every other column is `datagen_reads.py`'s, value for
value (tests/test_direct_table_budget.py holds them equal).
"""
from __future__ import annotations

import numpy as np
import pyarrow as pa

import datagen_reads

TABLES = datagen_reads.TABLES
#: the columns that hold an order's key, by table
ORDER_KEYS = {"orders": "o_orderkey", "lineitem": "l_orderkey"}


def sparse_key(i: np.ndarray) -> np.ndarray:
    """dbgen's mk_sparse: keep the low 3 bits of the index, leave 2 bits of
    gap above them (8 of every 32 values used)."""
    i = np.asarray(i, dtype=np.int64)
    return ((i >> 3) << 5) + (i & 7)


def gen_tables(sf: float, seed: int, tables=TABLES) -> dict:
    """{name: Arrow table} as datagen_reads.gen_tables gives it, the order
    keys mapped through `sparse_key`."""
    out = datagen_reads.gen_tables(sf=sf, seed=seed, tables=tables)
    for name, col in ORDER_KEYS.items():
        tbl = out.get(name)
        if tbl is None or col not in tbl.column_names:
            continue
        dense = tbl.column(col).to_numpy()
        out[name] = tbl.set_column(tbl.column_names.index(col), col,
                                   pa.array(sparse_key(dense),
                                            type=pa.int64()))
    return out
