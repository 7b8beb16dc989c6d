"""TPC-H Q16, "Parts/Supplier Relationship", as the benchmark's in-process
SF10 cell runs it (`benchmark/queries/q16.sql`, the specification's
validation text; deployment `embedded_fused`): `partsupp` joined to `part`
under `<>`, NOT LIKE and an IN list, a NOT IN over the suppliers whose
comment holds "Customer ... Complaints", and COUNT(DISTINCT ps_suppkey) per
(brand, type, size). Over `benchmark/datagen_spec_supplier.py`'s tables the
fused compiler accepts the plan and it runs as ONE program that equals the
benchmark's pandas reference (`benchmark/oracle/tpch_pandas_suppcnt.py`)
cell for cell. The generator writes exactly SF x 5 rows of each kind (at
least one). The comparison refuses an answer without the NOT IN, one that
counts suppliers without DISTINCT, and, where the subquery holds a NULL key,
any answer but no rows."""
import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pytest

from test_custdist import engine, query_text, verdict
from test_direct_table_budget import bench_module

TABLES = ["part", "partsupp", "supplier"]
SEED = 4600000301


@pytest.fixture(scope="module")
def staged():
    """sf -> Q16's tables at `sf`; one copy per scale for the module."""
    gen, tables = bench_module("datagen_spec_supplier"), {}

    def at(sf: float) -> dict:
        if sf not in tables:
            tables[sf] = gen.gen_tables(sf=sf, seed=SEED, tables=TABLES)
        return tables[sf]
    return at


def reference(tables: dict):
    compare = bench_module("compare")
    oracle = bench_module("oracle/tpch_pandas_suppcnt")
    return oracle.q16({n: compare.frame(t) for n, t in tables.items()}), \
        compare


def run(tables: dict, sql: str):
    """(answer, counter delta) of `sql` as `embedded_fused` runs it: the
    fused compiler's verdict first, then the query."""
    from igloo_tpu.utils import tracing
    eng = engine(tables)
    verdict(eng, sql)
    with tracing.counter_delta() as d:
        got = eng.execute(sql)
    return got, d


@pytest.mark.parametrize("sf", [0.05, 1.0])
def test_q16_is_one_program_and_equals_the_reference(staged, sf):
    """SF 0.05: 10,000 parts, 500 suppliers, both stages the packed sort;
    SF 1: 200,000 and 10,000, where stage 2's 200,226 segments (26 x 151 x
    51) are under twice stage 1's capacity and take the scatter, as in the
    SF10 cell."""
    tables = staged(sf)
    want, compare = reference(tables)
    assert len(want) > 100
    got, d = run(tables, query_text("q16"))
    # the main plan one program; the NOT IN's two guard scalars run first
    assert d.get("fused.execute") == 3 and not d.get("fused.unsupported")
    assert d.get("agg.distinct") == 1
    assert d.get("join.direct_routes") == 2      # part, and the anti probe
    scatters = int(sf >= 1)
    assert d.get("agg.direct_scatter") == scatters
    assert d.get("pack.agg") == 2 - scatters
    err, wrong, why = compare.compare(got, want)
    assert wrong == 0, why
    assert err == 0.0


@pytest.mark.parametrize("sf", [0.05, 0.4, 10.0])
def test_the_generator_writes_sf_x_5_rows_of_each_kind(sf):
    gen = bench_module("datagen_spec_supplier")
    n = bench_module("datagen")._counts(sf)["supplier"]
    if sf == 10.0:       # the cell's scale: the comments alone, no table
        comments = gen.supplier_comments(sf, SEED, n)
    else:
        tables = gen.gen_tables(sf=sf, seed=SEED, tables=["supplier"])
        base = bench_module("datagen_reads").gen_tables(
            sf=sf, seed=SEED, tables=["supplier"])
        sup = tables["supplier"]
        assert sup.column("s_suppkey").equals(
            base["supplier"].column("s_suppkey"))
        comments = sup.column("s_comment")
    k = max(1, round(sf * 5))
    assert gen.rows_of_each_kind(sf) == k
    for kind in ("Complaints", "Recommends"):
        hits = pc.match_like(comments, f"%Customer%{kind}%")
        assert pc.sum(hits).as_py() == k
    lengths = pc.utf8_length(comments).to_numpy()
    assert lengths.min() >= 25 and lengths.max() <= 100
    assert len(comments) == n


def test_the_comparison_refuses_each_control(staged):
    """At SF 1, where parts of one group share suppliers (at SF 0.05 the
    two counts hardly ever differ)."""
    tables = staged(1.0)
    want, compare = reference(tables)
    sql = query_text("q16")
    controls = {
        "no NOT IN": _without_not_in(sql),
        "COUNT for COUNT(DISTINCT)": sql.replace(
            "count(distinct ps_suppkey)", "count(ps_suppkey)"),
    }
    for name, text in controls.items():
        assert text != sql, name
        got, _ = run(tables, text)
        _, wrong, _ = compare.compare(got, want)
        assert wrong > 0, name
    # a NULL among the subquery's keys: NOT IN keeps no row, in the
    # engine and in the reference alike, and that empty answer is refused
    sup = tables["supplier"]
    bad = pc.match_like(sup.column("s_comment"), "%Customer%Complaints%")
    keys = np.where(bad.to_numpy(zero_copy_only=False), None,
                    sup.column("s_suppkey").to_numpy()).tolist()
    nulls = dict(tables, supplier=sup.set_column(
        0, "s_suppkey", pa.array(keys, type=pa.int64())))
    got, _ = run(nulls, sql)
    assert got.num_rows == 0
    assert len(reference(nulls)[0]) == 0
    _, wrong, _ = compare.compare(got, want)
    assert wrong > 0


def _without_not_in(sql: str) -> str:
    """The query text with its NOT IN predicate taken out."""
    head, rest = sql.split("\tand ps_suppkey not in (")
    return head + rest[rest.index(")\n") + 2:]
