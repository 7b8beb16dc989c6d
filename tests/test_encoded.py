"""Compressed execution end to end (docs/compressed_execution.md): the
Arrow-side carrier codec (exec/encoded.py), the run-length transfer carrier,
the encoded exchange store, and the `IGLOO_TPU_ENCODED=0` kill switch.

The kill switch claims BIT-identical results, so every encoded-vs-plain A/B
below compares `to_pydict()` with exact `==` — floats included. Tier A/Bs
build a FRESH engine per setting: scan/jit caches are carrier-aware
(batch prototypes fingerprint the carrier form), but a cached device batch
uploaded under one setting must not serve the other side's measurement."""
import os
import threading

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from igloo_tpu.catalog import MemTable
from igloo_tpu.cluster import exchange
from igloo_tpu.engine import QueryEngine
from igloo_tpu.exec import codec, encoded
from igloo_tpu.utils import tracing


# --- Arrow carrier codec (exec/encoded.py) -----------------------------------


def _mixed_table(n=101):
    rng = np.random.default_rng(5)
    return pa.table({
        "k": pa.array([1_000_000 + i for i in range(n - 1)] + [None],
                      type=pa.int64()),
        "s": pa.array((["a", "b", None, "c"] * n)[:n], type=pa.string()),
        "p": pa.array([round(float(x), 2) for x in rng.random(n - 1) * 100]
                      + [None], type=pa.float64()),
        "d": pa.array([18_000 + i % 40 for i in range(n)],
                      type=pa.int32()).cast(pa.date32()),
        "ts": pa.array([1_600_000_000_000_000 + i * 1_000_000
                        for i in range(n)],
                       type=pa.int64()).cast(pa.timestamp("us")),
    })


def test_roundtrip_all_lanes():
    t = _mixed_table()
    enc = encoded.encode_table(t, strings=True)
    # every lane actually narrowed / dictionary-encoded
    assert enc.schema.field("k").type == pa.int8()
    assert pa.types.is_dictionary(enc.schema.field("s").type)
    assert pa.types.is_integer(enc.schema.field("p").type)  # scaled-decimal
    assert enc.nbytes < t.nbytes
    dec = encoded.decode_table(enc)
    assert dec.schema.equals(t.schema)
    assert dec.equals(t)
    # decode is a no-op on plain tables (self-describing contract)
    assert encoded.decode_table(t) is t


def test_two_phase_slices_share_schema_and_roundtrip():
    """The exchange shape: strings encode ONCE, slices encode numerics under
    ONE global plan — every slice gets the identical schema and the
    reassembled decode is the original table."""
    t = _mixed_table()
    se = encoded.encode_strings(t)
    plan = encoded.plan_numeric(se)
    a, b = se.slice(0, 40), se.slice(40)
    ea, eb = encoded.apply_numeric(a, plan), encoded.apply_numeric(b, plan)
    assert ea.schema.equals(eb.schema)
    assert encoded.decode_table(pa.concat_tables([ea, eb])).equals(t)


def test_offset_straddling_zero():
    v = list(range(-500, 501)) + [None]
    t = pa.table({"x": pa.array(v, type=pa.int64())})
    enc = encoded.encode_table(t)
    assert enc.schema.field("x").type == pa.int16()
    assert encoded.decode_table(enc).equals(t)
    assert encoded.column_min_max(enc, "x") == (-500, 500)
    assert encoded.column_min_max(t, "x") == (-500, 500)


def test_nan_lanes_never_lose_bits():
    """NaN disables scaled-decimal (a NaN*scale roundtrip cannot verify) but
    may still ride the exact-f32 carrier; either way decode is bit-exact and
    NaN stays a VALUE, not a null."""
    t = pa.table({"x": pa.array([1.5, float("nan"), -2.25, None, 0.0],
                                type=pa.float64())})
    enc = encoded.encode_table(t)
    dec = encoded.decode_table(enc)
    assert dec.column("x").null_count == 1
    got = np.asarray(dec.column("x").combine_chunks().fill_null(7.0))
    want = np.asarray(t.column("x").combine_chunks().fill_null(7.0))
    np.testing.assert_array_equal(got, want)  # equal_nan for ==
    assert np.array_equal(got, want, equal_nan=True)


def test_empty_table_and_empty_dictionary():
    t = _mixed_table().slice(0, 0)
    enc = encoded.encode_table(t, strings=True)
    assert encoded.decode_table(enc).equals(t)
    assert encoded.column_min_max(enc, "k") is None
    # all-null string column: an EMPTY dictionary after encoding
    s = pa.table({"s": pa.array([None, None, None], type=pa.string()),
                  "i": pa.array([5, 6, 7], type=pa.int64())})
    es = encoded.encode_table(s, strings=True)
    assert pa.types.is_dictionary(es.schema.field("s").type)
    assert encoded.decode_table(es).equals(s)
    # all-null int column is left alone (no range to prove)
    assert es.schema.field("i").type == pa.int64() or \
        encoded.decode_table(es).column("i").to_pylist() == [5, 6, 7]


def test_kill_switch_is_a_noop(monkeypatch):
    monkeypatch.setenv("IGLOO_TPU_ENCODED", "0")
    t = _mixed_table()
    assert encoded.encode_table(t, strings=True) is t
    assert encoded.encode_strings(t) is t
    assert encoded.plan_numeric(t) == {}
    assert not codec.encoded_enabled()
    assert not codec.rle_enabled()  # ENCODED=0 implies RLE off


# --- run-length transfer carrier ---------------------------------------------


def test_rle_roundtrip_host():
    arr = np.repeat(np.arange(40, dtype=np.int64), 128)  # 5120 rows, 40 runs
    rv, starts = codec.rle_encode(arr)
    assert len(rv) == 40 and starts[0] == 0
    np.testing.assert_array_equal(codec.rle_decode(rv, starts, len(arr)), arr)
    # refusals: too short, too many runs, non-integer
    assert codec.rle_encode(arr[:1000]) is None
    assert codec.rle_encode(np.arange(5000, dtype=np.int64)) is None
    assert codec.rle_encode(np.zeros(5000, dtype=np.float64)) is None


def test_rle_device_expand_matches_host():
    arr = np.repeat(np.arange(17, dtype=np.int16), 100)  # 1700 rows
    rv, starts = codec.rle_encode(arr)
    cap = 2048
    runs_cap = codec.round_capacity_for_runs(len(rv))
    prv = np.zeros(runs_cap, dtype=rv.dtype)
    prv[: len(rv)] = rv
    pst = np.full(runs_cap, cap, dtype=np.int32)
    pst[: len(starts)] = starts
    out = np.asarray(codec._rle_expand_jit(runs_cap, cap, rv.dtype.name)(
        prv, pst))
    np.testing.assert_array_equal(out[: len(arr)], arr)


def test_rle_through_upload_columns():
    """A sorted narrow column ships as (run values, run starts) and the
    resident carrier still widens to the exact original."""
    arr = np.repeat(np.arange(8, dtype=np.int64) * 3 + 100, 512)  # 4096 rows
    cap = 4096
    with tracing.counter_delta() as delta:
        (vals, spec, carg), = codec.upload_columns([(arr, np.int64, cap)])
    assert delta.get("codec.rle_columns") == 1
    assert delta.get("codec.carrier_bytes") < delta.get("codec.decoded_bytes")
    wide = codec.host_widen(spec, np.asarray(vals),
                            np.asarray(carg) if carg is not None else None)
    np.testing.assert_array_equal(wide[: len(arr)], arr)
    assert wide.dtype == np.int64


# --- decimal canary: thread-safe + test-visible reset ------------------------


def test_decimal_canary_reset_hook_and_thread_safety():
    codec.reset_decimal_canary()
    assert codec._decimal_canary_ok is None
    results = []
    threads = [threading.Thread(
        target=lambda: results.append(codec._scaled_decimal_ok()))
        for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    # every racer saw the SAME settled verdict (True on CPU's IEEE divide)
    assert results == [True] * 8
    assert codec._decimal_canary_ok is True
    # a poisoned canary stays poisoned until the reset hook re-arms it
    with codec._canary_lock:
        codec._decimal_canary_ok = False
    assert codec._scaled_decimal_ok() is False
    codec.reset_decimal_canary()
    assert codec._scaled_decimal_ok() is True


# --- encoded exchange store --------------------------------------------------


def _orders(n=600):
    rng = np.random.default_rng(7)
    return pa.table({
        "cust": pa.array(rng.integers(0, 50, n) + 10_000, type=pa.int64()),
        "tier": pa.array([["gold", "silver", "bronze"][i % 3]
                          for i in range(n)]),
        "total": pa.array([round(float(x), 2) for x in rng.random(n) * 100],
                          type=pa.float64()),
    })


def test_exchange_put_unifies_dictionaries_and_narrows():
    """Satellite of the tentpole: a partitioned put dictionary-encodes each
    string column ONCE — every bucket's record batches share the single
    unified dictionary buffer — and numeric slices narrow under one global
    spec; each decoded bucket equals the plain partitioning of the input."""
    t = _orders()
    nb = 4
    store = exchange.FragmentStore(budget_bytes=1 << 24)
    ent = store.put("f1", t, partition=([0], nb))
    sfield = ent.schema.field("tier")
    assert pa.types.is_dictionary(sfield.type)
    assert ent.schema.field("cust").type in (pa.int8(), pa.int16())
    dict_addrs = set()
    for b in ent.batches:
        col = b.column(ent.schema.get_field_index("tier"))
        dict_addrs.add(col.dictionary.buffers()[-1].address)
    assert len(dict_addrs) == 1, "bucket batches rebuilt their dictionaries"
    plain = exchange.partition_table(t, [0], nb)
    for i in range(nb):
        got = encoded.decode_table(store.get_table("f1", i, nb))
        assert got.equals(plain[i]), f"bucket {i}"
    # non-partitioned (coordinator-facing) results stay plain
    ent2 = store.put("f2", t)
    assert ent2.schema.equals(t.schema)


def test_exchange_encoded_bytes_beat_plain(monkeypatch):
    t = _orders(2000)
    enc_ent = exchange.FragmentStore(1 << 24).put("f", t, partition=([0], 4))
    monkeypatch.setenv("IGLOO_TPU_ENCODED", "0")
    plain_ent = exchange.FragmentStore(1 << 24).put("f", t,
                                                    partition=([0], 4))
    assert plain_ent.schema.equals(t.schema)
    assert enc_ent.nbytes < 0.7 * plain_ent.nbytes, \
        (enc_ent.nbytes, plain_ent.nbytes)
    # identical logical rows either way
    for i in range(4):
        a = encoded.decode_table(pa.Table.from_batches(
            enc_ent.batches[slice(*[enc_ent.ranges[i][0],
                                    enc_ent.ranges[i][0]
                                    + enc_ent.ranges[i][1]])],
            schema=enc_ent.schema))
        b = pa.Table.from_batches(
            plain_ent.batches[plain_ent.ranges[i][0]:
                              plain_ent.ranges[i][0] + plain_ent.ranges[i][1]],
            schema=plain_ent.schema)
        assert a.to_pydict() == b.to_pydict(), f"bucket {i}"


# --- tier A/Bs: encoded vs kill switch must be row-identical -----------------


def _device_tables(n=4096):
    rng = np.random.default_rng(11)
    fact = pa.table({
        "fk": pa.array(rng.integers(1, 400, n) + 5_000, type=pa.int64()),
        "grp": pa.array(np.repeat(np.arange(16, dtype=np.int64), n // 16)),
        "v": pa.array([round(float(x), 2) for x in rng.random(n) * 100],
                      type=pa.float64()),
        "day": pa.array(rng.integers(18_000, 18_060, n).astype(np.int32),
                        type=pa.int32()).cast(pa.date32()),
    })
    dim = pa.table({
        "k": pa.array(np.arange(1, 401, dtype=np.int64) + 5_000),
        "name": pa.array([f"n{i % 37:02d}" for i in range(400)]),
        "w": pa.array([round(float(x), 2) for x in
                       np.random.default_rng(3).random(400) * 10],
                      type=pa.float64()),
    })
    return fact, dim


DEVICE_SQL = """
    SELECT d.name, COUNT(*) AS n, SUM(f.v * d.w) AS s, MIN(f.day) AS d0
    FROM fact f JOIN dim d ON f.fk = d.k
    WHERE f.v > 5 AND f.grp < 14
    GROUP BY d.name ORDER BY d.name
"""


def _device_engine():
    e = QueryEngine()
    fact, dim = _device_tables()
    e.register_table("fact", MemTable(fact))
    e.register_table("dim", MemTable(dim))
    return e


def test_device_tier_ab_and_counters(monkeypatch):
    monkeypatch.delenv("IGLOO_TPU_ENCODED", raising=False)
    with tracing.counter_delta() as enc_delta:
        got = _device_engine().execute(DEVICE_SQL)
    assert enc_delta.get("codec.carrier_bytes") > 0
    assert enc_delta.get("codec.carrier_bytes") < \
        enc_delta.get("codec.decoded_bytes")
    assert enc_delta.get("codec.rle_columns") >= 1  # sorted `grp` column
    monkeypatch.setenv("IGLOO_TPU_ENCODED", "0")
    with tracing.counter_delta() as plain_delta:
        want = _device_engine().execute(DEVICE_SQL)
    assert plain_delta.get("codec.carrier_bytes") == \
        plain_delta.get("codec.decoded_bytes")
    assert enc_delta.get("xfer.h2d_bytes") < plain_delta.get("xfer.h2d_bytes")
    assert got.to_pydict() == want.to_pydict()


@pytest.fixture(scope="module")
def ooc_parquet(tmp_path_factory):
    d = tmp_path_factory.mktemp("encoded_ooc")
    fact, dim = _device_tables(n=24_000)
    pq.write_table(fact, os.path.join(d, "fact.parquet"),
                   row_group_size=3000)
    pq.write_table(dim, os.path.join(d, "dim.parquet"), row_group_size=100)
    return d


def _parquet_engine(d, budget):
    from igloo_tpu.connectors.parquet import ParquetTable
    e = QueryEngine(chunk_budget_bytes=budget)
    e.register_table("fact", ParquetTable(os.path.join(d, "fact.parquet")))
    e.register_table("dim", ParquetTable(os.path.join(d, "dim.parquet")))
    return e


CHUNKED_SQL = """
    SELECT grp, COUNT(*) AS n, SUM(v) AS s FROM fact
    WHERE v > 2 GROUP BY grp ORDER BY grp
"""


def test_chunked_tier_ab(ooc_parquet, monkeypatch):
    monkeypatch.delenv("IGLOO_TPU_ENCODED", raising=False)
    with tracing.counter_delta() as d1:
        got = _parquet_engine(ooc_parquet, 64 << 10).execute(CHUNKED_SQL)
    assert d1.get("engine.chunked_route") == 1, "budget did not force chunked"
    monkeypatch.setenv("IGLOO_TPU_ENCODED", "0")
    with tracing.counter_delta() as d2:
        want = _parquet_engine(ooc_parquet, 64 << 10).execute(CHUNKED_SQL)
    assert d2.get("engine.chunked_route") == 1
    assert got.to_pydict() == want.to_pydict()


def test_grace_tier_ab(ooc_parquet, monkeypatch):
    monkeypatch.delenv("IGLOO_TPU_ENCODED", raising=False)
    with tracing.counter_delta() as d1:
        got = _parquet_engine(ooc_parquet, 256 << 10).execute(DEVICE_SQL)
    assert d1.get("engine.grace_route") == 1, "budget did not force GRACE"
    assert d1.get("grace.partition_bytes") > 0
    monkeypatch.setenv("IGLOO_TPU_ENCODED", "0")
    with tracing.counter_delta() as d2:
        want = _parquet_engine(ooc_parquet, 256 << 10).execute(DEVICE_SQL)
    assert d2.get("engine.grace_route") == 1
    # GRACE partition buffers held fewer bytes in carrier form
    assert d1.get("grace.partition_bytes") < d2.get("grace.partition_bytes")
    assert got.to_pydict() == want.to_pydict()


# --- 2-worker shuffle A/B (slow: spins two in-process clusters) --------------


def test_shuffle_ab_two_workers(monkeypatch):
    """The fourth tier: a real 2-worker distributed join, encoded vs kill
    switch — identical rows, measurably fewer exchange bytes encoded."""
    import time

    from igloo_tpu.cluster.client import DistributedClient
    from igloo_tpu.cluster.coordinator import CoordinatorServer
    from igloo_tpu.cluster.worker import Worker

    # adaptive stats from run 1 would flip run 2's join to broadcast
    # (shuffle_buckets == 0) and void the exchange-bytes comparison
    monkeypatch.setenv("IGLOO_ADAPTIVE", "0")
    fact, dim = _device_tables(n=2048)
    sql = ("SELECT f.fk, d.name, f.v FROM fact f JOIN dim d ON f.fk = d.k "
           "WHERE f.v > 50 ORDER BY f.fk, f.v")

    def run():
        coord = CoordinatorServer("grpc+tcp://127.0.0.1:0",
                                  worker_timeout_s=60.0, use_jit=False)
        caddr = f"127.0.0.1:{coord.port}"
        workers = [Worker(caddr, port=0, heartbeat_interval_s=0.5,
                          use_jit=False) for _ in range(2)]
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
            got = client.execute(sql)
            m = client.last_metrics()
            client.close()
            return got, m
        finally:
            for w in workers:
                w.shutdown()
            coord.shutdown()

    monkeypatch.delenv("IGLOO_TPU_ENCODED", raising=False)
    got_enc, m_enc = run()
    monkeypatch.setenv("IGLOO_TPU_ENCODED", "0")
    got_plain, m_plain = run()
    assert got_enc.to_pydict() == got_plain.to_pydict()
    assert m_enc["shuffle_buckets"] >= 2 and m_plain["shuffle_buckets"] >= 2
    assert m_enc["exchange_bytes"] < m_plain["exchange_bytes"], \
        (m_enc["exchange_bytes"], m_plain["exchange_bytes"])
