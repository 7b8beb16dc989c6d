"""The f32-pair carrier (exec/codec.py, ISSUE 37) through everything that
moves rows.

A float64 lane no narrower carrier takes is resident as two `[capacity]` f32
lanes where the device's float64 is that pair (the v5e). XLA:CPU says no, so
these tests force the codec into the chip's state — the decimal canary
failed, the pair canary passed — and hold every row mover to the same
engine's answer with both verdicts no (columns wide, as the CPU runs them):
equal rows, floats within 1e-12 (a pair keeps 48 of 53 bits)."""
import os

import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from igloo_tpu.catalog import MemTable
from igloo_tpu.engine import QueryEngine
from igloo_tpu.exec import codec
from igloo_tpu.exec import kernels as K
from igloo_tpu.exec.batch import from_arrow, to_arrow, wide_values
from igloo_tpu.utils import tracing

REL = 1e-12


@pytest.fixture
def verdicts(monkeypatch):
    """`verdicts(pair)`: the chip's codec (decimal canary failed) with the
    pair canary's verdict set."""
    def set_(pair: bool):
        monkeypatch.setattr(codec, "_decimal_canary_ok", False)
        monkeypatch.setattr(codec, "_f32pair_canary_ok", pair)
    return set_


def _assert_rows_close(got: pa.Table, want: pa.Table):
    assert got.schema.names == want.schema.names
    assert got.num_rows == want.num_rows
    for name in want.schema.names:
        g, w = got.column(name).to_pylist(), want.column(name).to_pylist()
        if pa.types.is_floating(want.schema.field(name).type):
            assert [x is None for x in g] == [x is None for x in w], name
            gv = np.asarray([x for x in g if x is not None], dtype=float)
            wv = np.asarray([x for x in w if x is not None], dtype=float)
            np.testing.assert_allclose(gv, wv, rtol=REL, atol=0, err_msg=name)
        else:
            assert g == w, name


def _prices(n, seed=0, nulls=False):
    v = np.round(np.random.default_rng(seed).uniform(900.0, 105000.0, n), 2)
    mask = (np.arange(n) % 11 == 3) if nulls else None
    return pa.array(v, type=pa.float64(), mask=mask)


def _pair_batch(n=2000, nulls=True):
    t = pa.table({"p": _prices(n, 1, nulls), "i": pa.array(np.arange(n))})
    b = from_arrow(t)
    assert b.columns[0].is_pair
    return t, b


# --- the row movers of exec/kernels.py ---------------------------------------

def test_gather_perm_resize_compact_widen_a_pair_first(verdicts):
    """A pair is the form a column is resident and read in; a row mover
    widens it in-trace and hands on one wide lane (`DeviceColumn.map_rows`)."""
    verdicts(True)
    t, b = _pair_batch()
    want = t.column("p").to_pylist()
    n, cap = t.num_rows, b.capacity

    def rows(batch):
        col = batch.columns[0]
        assert col.carrier is None and col.carrier_arg is None
        assert col.values.dtype == jnp.float64
        return to_arrow(batch).column("p").to_pylist()

    def close(got, want_):
        assert [x is None for x in got] == [x is None for x in want_]
        np.testing.assert_allclose(
            [x for x in got if x is not None],
            [x for x in want_ if x is not None], rtol=REL, atol=0)

    perm = jnp.asarray(np.random.default_rng(2).permutation(cap))
    close(rows(K.apply_perm(b, perm)),
          [want[i] for i in np.asarray(perm) if i < n])
    idx = jnp.asarray(np.arange(0, 64) * 7)  # a gather to fewer lanes
    g = K.gather_batch(b, idx)[0]
    assert g.carrier is None and g.values.shape == (64,)
    close(np.asarray(g.values)[~np.asarray(g.nulls)].tolist(),
          [want[i * 7] for i in range(64) if want[i * 7] is not None])
    for capacity in (cap * 2, cap // 2):
        r = K.resize_batch(b, capacity)
        assert r.columns[0].values.shape == (capacity,)
        close(rows(r), want[:capacity])
    assert K.resize_batch(b, cap) is b  # nothing moved: still the pair
    keep = b.live & (jnp.arange(cap) % 2 == 0)
    from dataclasses import replace
    c = K.compact_to(replace(b, live=keep), 1024)
    assert c.columns[0].values.shape == (1024,)
    close(rows(c), want[::2])
    # a dead or pad lane widens to 0
    assert float(jnp.sum(jnp.abs(wide_values(b.columns[0])[n:]))) == 0.0
    # parts of an outer join: one that moved rows (wide), one that did not
    moved = K.gather_batch(b, jnp.arange(cap))
    [both, _i] = K.concat_columns([b.columns, moved])
    assert both.carrier is None and both.values.shape == (2 * cap,)
    close(np.asarray(both.values)[:n][~np.asarray(both.nulls)[:n]].tolist(),
          [x for x in want if x is not None])


def test_bytes_count_a_pair_once_at_eight_a_lane(verdicts):
    verdicts(True)
    t = pa.table({"p": _prices(2000, 3)})
    b = from_arrow(t)
    cap = b.capacity
    assert b.columns[0].nbytes == 8 * cap
    assert b.nbytes() == 8 * cap + cap  # + the live lane
    e = QueryEngine()
    e.register_table("t", MemTable(t))
    with tracing.counter_delta() as d:
        e.execute("SELECT SUM(p) AS s FROM t")
    assert d.get("codec.f32pair_columns") == 1
    assert d.get("codec.carrier_bytes") == d.get("codec.decoded_bytes") \
        == 8 * cap
    assert d.get("xfer.h2d_bytes") >= 8 * cap
    resident = sum(ent.nbytes for key, ent in e.batch_cache._entries.items()
                   if "col" in key)
    assert resident == 8 * cap


# --- joins, union, sort through the engine, both compilers --------------------

def _tables(n=3000):
    rng = np.random.default_rng(5)
    fact = pa.table({
        "fk": pa.array(rng.integers(0, 1700, n), type=pa.int64()),
        "fkf": pa.array(rng.integers(0, 24, n) * 0.5 + 0.1, type=pa.float64(),
                        mask=np.arange(n) % 97 == 0),
        "v": _prices(n, 6, nulls=True),
        "g": pa.array(np.arange(n) % 5, type=pa.int64()),
    })
    dim = pa.table({
        "k": pa.array(np.arange(1500), type=pa.int64()),
        "kf": pa.array(np.arange(1500) % 16 * 0.5 + 0.1, type=pa.float64()),
        "w": _prices(1500, 7),
    })
    return fact, dim


QUERIES = {
    # a direct (array) join: gathers of the build side's pair column, null
    # pads and the concatenation of an outer join's parts
    "direct_left": "SELECT f.fk, f.v, d.w FROM fact f LEFT JOIN dim d "
                   "ON f.fk = d.k ORDER BY f.fk, f.v, d.w",
    "direct_inner": "SELECT f.g, SUM(f.v * d.w) AS s, COUNT(*) AS n "
                    "FROM fact f JOIN dim d ON f.fk = d.k "
                    "WHERE d.w > 5000 GROUP BY f.g ORDER BY f.g",
    # float keys (themselves pair columns) take no direct table: sorted probe
    "sorted_probe": "SELECT f.fk, f.v, d.w FROM fact f JOIN dim d "
                    "ON f.fkf = d.kf WHERE f.fk < 40 AND d.k < 64 "
                    "ORDER BY f.fk, f.v, d.w",
    "union": "SELECT v AS x FROM fact WHERE g = 1 UNION ALL "
             "SELECT w AS x FROM dim ORDER BY x",
    "sort_limit": "SELECT v, fk FROM fact ORDER BY v DESC, fk LIMIT 50",
    "bare_scan": "SELECT * FROM dim",
}


def _run(sql, compiler):
    fact, dim = _tables()
    e = QueryEngine()
    e.register_table("fact", MemTable(fact))
    e.register_table("dim", MemTable(dim))
    if compiler == "fused":
        return e.execute(sql)
    from igloo_tpu.exec.executor import Executor
    ex = Executor(e._jit_cache, batch_cache=e.batch_cache)
    return ex._staged_to_arrow(e.plan(sql))


@pytest.mark.parametrize("compiler", ["fused", "staged"])
@pytest.mark.parametrize("name", sorted(QUERIES))
def test_row_movers_give_the_wide_runs_rows(verdicts, name, compiler):
    verdicts(False)
    with tracing.counter_delta() as d0:
        want = _run(QUERIES[name], compiler)
    assert not d0.get("codec.f32pair_columns")
    assert d0.get("codec.f64_wide_columns") >= 1
    verdicts(True)
    with tracing.counter_delta() as d1:
        got = _run(QUERIES[name], compiler)
    assert d1.get("codec.f32pair_columns") >= 1, "the carrier did not engage"
    assert not d1.get("codec.f64_wide_columns")
    _assert_rows_close(got, want)


@pytest.mark.parametrize("compiler", ["fused", "staged"])
def test_q1_folds_its_float_sums_as_pairs_where_float64_is_a_pair(verdicts,
                                                                  compiler):
    """ISSUE 39: under the chip's verdicts q1's five distinct float64 sums
    (the AVGs repeat three of them) are folded as f32 pairs — quantity from
    its int8 carrier, price and discount from their resident halves, the two
    products split in-trace — and the rows are the wide run's within 1e-12;
    under XLA:CPU's own verdict no lane is, and the counter is absent."""
    from igloo_tpu.bench.tpch import QUERIES as TPCH, gen_tables
    lineitem = gen_tables(sf=0.002)["lineitem"]

    def q1():
        e = QueryEngine()
        e.register_table("lineitem", lineitem)
        with tracing.counter_delta() as d:
            if compiler == "fused":
                return e.execute(TPCH["q1"]), d
            from igloo_tpu.exec.executor import Executor
            ex = Executor(e._jit_cache, batch_cache=e.batch_cache)
            return ex._staged_to_arrow(e.plan(TPCH["q1"])), d

    verdicts(False)
    want, d0 = q1()
    assert d0.get("agg.onepass_lanes") and "agg.pair_sum_lanes" not in d0
    verdicts(True)
    got, d1 = q1()
    assert d1.get("agg.pair_sum_lanes") == 5
    assert d1.get("codec.f32pair_columns") >= 2
    _assert_rows_close(got, want)


def test_explain_analyze_names_the_pair_columns(verdicts):
    verdicts(True)
    fact, _dim = _tables()
    e = QueryEngine()
    e.register_table("fact", MemTable(fact))
    text = e.execute("EXPLAIN ANALYZE SELECT SUM(v) AS s FROM fact "
                     "WHERE fk < 100").to_pydict()
    blob = "\n".join(str(x) for col in text.values() for x in col)
    assert "f32pair_columns" in blob and "'v'" in blob


# --- out of core: GRACE spill -------------------------------------------------

@pytest.fixture(scope="module")
def ooc_parquet(tmp_path_factory):
    d = tmp_path_factory.mktemp("f32pair_ooc")
    fact, dim = _tables(n=24_000)
    pq.write_table(fact, os.path.join(d, "fact.parquet"),
                   row_group_size=3000)
    pq.write_table(dim, os.path.join(d, "dim.parquet"), row_group_size=100)
    return d


def test_grace_spill_gives_the_wide_runs_rows(verdicts, ooc_parquet):
    from igloo_tpu.connectors.parquet import ParquetTable

    def run():
        e = QueryEngine(chunk_budget_bytes=256 << 10)
        for t in ("fact", "dim"):
            e.register_table(t, ParquetTable(
                os.path.join(ooc_parquet, t + ".parquet")))
        with tracing.counter_delta() as d:
            out = e.execute(QUERIES["direct_inner"])
        assert d.get("engine.grace_route") == 1, "budget did not force GRACE"
        return out, d
    verdicts(False)
    want, _ = run()
    verdicts(True)
    got, d = run()
    assert d.get("codec.f32pair_columns") >= 1
    _assert_rows_close(got, want)


# --- the fragment exchange round trip -----------------------------------------

def test_fragment_exchange_round_trip_gives_the_wide_runs_rows(
        verdicts, monkeypatch):
    """A 2-worker shuffle join: scan fragments hold pair columns, their
    results cross the exchange as Arrow float64 (`f64(hi) + f64(lo)` at the
    output boundary) and are uploaded again by the join fragments."""
    import time

    from igloo_tpu.cluster.client import DistributedClient
    from igloo_tpu.cluster.coordinator import CoordinatorServer
    from igloo_tpu.cluster.worker import Worker
    monkeypatch.setenv("IGLOO_ADAPTIVE", "0")
    fact, dim = _tables(n=2048)
    sql = ("SELECT f.fk, d.w, f.v FROM fact f JOIN dim d ON f.fk = d.k "
           "WHERE f.v > 50000 ORDER BY f.fk, f.v")

    def run():
        coord = CoordinatorServer("grpc+tcp://127.0.0.1:0",
                                  worker_timeout_s=60.0)
        caddr = f"127.0.0.1:{coord.port}"
        workers = [Worker(caddr, port=0, heartbeat_interval_s=0.5)
                   for _ in range(2)]
        try:
            for w in workers:
                w.start()
            deadline = time.time() + 20
            while len(coord.membership.live()) < 2 and \
                    time.time() < deadline:
                time.sleep(0.05)
            coord.register_table("fact", MemTable(fact, partitions=2))
            coord.register_table("dim", MemTable(dim, partitions=2))
            client = DistributedClient(caddr)
            before = tracing.counters().get("codec.f32pair_columns", 0)
            got = client.execute(sql)
            m = client.last_metrics()
            client.close()
            return got, m, \
                tracing.counters().get("codec.f32pair_columns", 0) - before
        finally:
            for w in workers:
                w.shutdown()
            coord.shutdown()
    verdicts(False)
    want, _m, n0 = run()
    verdicts(True)
    got, m, n1 = run()
    assert n0 == 0 and n1 >= 2
    assert m["shuffle_buckets"] >= 2
    _assert_rows_close(got, want)


# --- the mesh boundary ---------------------------------------------------------

def test_mesh_tier_widens_a_pair_at_its_boundary(verdicts):
    """`parallel/mesh.py _put_batch` materializes every carrier before it
    shards a batch (a carrier arg takes no row-partitioned spec): a pair's
    halves are widened there like any other carrier."""
    from igloo_tpu.parallel.mesh import make_mesh
    sql = "SELECT g, SUM(v) AS s, COUNT(*) AS n FROM fact GROUP BY g ORDER BY g"

    def run():
        fact, _dim = _tables()
        e = QueryEngine(mesh=make_mesh(4))
        e.register_table("fact", MemTable(fact))
        with tracing.counter_delta() as d:
            return e.execute(sql), d
    verdicts(False)
    want, _ = run()
    verdicts(True)
    got, d = run()
    assert d.get("codec.f32pair_columns") >= 1
    _assert_rows_close(got, want)
