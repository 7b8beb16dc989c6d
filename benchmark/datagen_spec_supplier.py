"""`datagen_reads.py`'s tables with `S_COMMENT` as TPC-H's own text.

Clause 4.2.3: S_COMMENT is a text string [25, 100] (a substring of the
clause 4.2.2.14 pool, as `datagen_spec_text.py` draws O_COMMENT), and of
the SF x 10,000 suppliers SF x 5 rows, chosen at random, hold "Customer"
and later "Complaints" at a random position, and another SF x 5 hold
"Customer" and later "Recommends". TPC-H Q16 drops the suppliers of the
first kind (`s_comment LIKE '%Customer%Complaints%'`); `datagen.py`'s
comments, 2-6 words of a pool of 27, never hold either.

The two words are written as dbgen's `mk_supp` writes them: "Customer "
at an offset, the type word after a gap of `noise` characters, both over
the text, so the comment keeps its length. The pool's words never spell
"Customer", "Complaints" or "Recommends", so the rows chosen are the only
ones that match. At least one row of each kind is chosen at every scale
(SF x 5 rounded, at least 1).

Every other column is `datagen_reads`', value for value; the comments and
the rows chosen draw from a generator of their own, seeded from
(seed, "s_comment").
"""
from __future__ import annotations

import pyarrow as pa

import datagen
import datagen_reads
from datagen_spec_text import text_pool, text_strings

TABLES = datagen_reads.TABLES
#: S_COMMENT is text string [25, 100] (clause 4.2.3)
COMMENT_LEN = (25, 100)
BASE = "Customer "
KINDS = ("Complaints", "Recommends")


def rows_of_each_kind(sf: float) -> int:
    """SF x 5, rounded, and at least one."""
    return max(1, round(sf * 5))


def _press(rng, text: str, kind: str) -> str:
    """`text` with BASE at a random offset and `kind` after a random gap,
    written over it (dbgen's mk_supp)."""
    room = len(text) - len(BASE) - len(kind)
    noise = int(rng.integers(0, room + 1))
    at = int(rng.integers(0, room - noise + 1))
    after = at + len(BASE) + noise
    return (text[:at] + BASE + text[at + len(BASE):after] + kind
            + text[after + len(kind):])


def supplier_comments(sf: float, seed: int, n: int) -> pa.Array:
    """The n suppliers' comments: clause 4.2.3's text, SF x 5 of them with
    "Customer ... Complaints" and SF x 5 with "Customer ... Recommends"."""
    rng = datagen._rng(seed, "s_comment")
    lo, hi = COMMENT_LEN
    text = text_strings(rng, text_pool(seed), n, lo, hi).to_numpy(
        zero_copy_only=False)
    k = rows_of_each_kind(sf)
    chosen = rng.choice(n, size=2 * k, replace=False)
    for i, row in enumerate(chosen):
        text[row] = _press(rng, text[row], KINDS[i // k])
    return pa.array(text, type=pa.string())


def gen_tables(sf: float, seed: int, tables=TABLES) -> dict:
    """{name: Arrow table} as datagen_reads.gen_tables gives it, with
    `s_comment` drawn by `supplier_comments`."""
    out = datagen_reads.gen_tables(sf=sf, seed=seed, tables=tables)
    tbl = out.get("supplier")
    if tbl is not None and "s_comment" in tbl.column_names:
        out["supplier"] = tbl.set_column(
            tbl.column_names.index("s_comment"), "s_comment",
            supplier_comments(sf, seed, tbl.num_rows))
    return out
