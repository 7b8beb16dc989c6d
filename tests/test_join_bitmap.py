"""The lazy direct join's full-width probe reads the positional table's
occupancy bits (exec/join.py direct_bitmap_probe) and the table itself only
at the hinted width (exec/fused.py _c_join_direct). The match mask is
direct_probe's, lane for lane, and the row ids the compaction keeps are the
ones direct_probe gives: the same rows, bit for bit. A join with a second
key pair or a residual needs the row id at full width and keeps
direct_probe (`join.bitmap_probes` does not move)."""
import jax.numpy as jnp
import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

from igloo_tpu import types as T
from igloo_tpu.engine import QueryEngine
from igloo_tpu.exec import join as J
from igloo_tpu.exec import kernels as K
from igloo_tpu.exec.batch import DeviceBatch, DeviceColumn
from igloo_tpu.exec.capacity import canonical_direct_table
from igloo_tpu.exec.expr_compile import Compiled
from igloo_tpu.utils import tracing

KEY = Compiled(fn=lambda env: (env.values[0], env.nulls[0]), dtype=T.INT64)
SCHEMA = T.Schema([T.Field("k", T.INT64, True)])


def batch(keys, live=None, nulls=None, cap=None) -> DeviceBatch:
    """One int64 key column, padded to `cap` lanes (dead past the keys)."""
    n = len(keys)
    cap = cap or max(8, 1 << (n - 1).bit_length())
    vals = np.zeros(cap, np.int64)
    vals[:n] = keys
    lv = np.zeros(cap, bool)
    lv[:n] = True if live is None else live
    nl = None
    if nulls is not None:
        nl = np.zeros(cap, bool)
        nl[:n] = nulls
        nl = jnp.asarray(nl)
    return DeviceBatch(SCHEMA, [DeviceColumn(T.INT64, jnp.asarray(vals), nl)],
                       jnp.asarray(lv))


def spec_key(i):
    """The spec's sparse order keys (8 of every 32 values), from 1."""
    i = np.asarray(i)
    return 32 * (i // 8) + i % 8 + 1


def _edges():
    lo, tsize = 100, 1 << 10
    slots = [0, 31, 32, tsize - 1, 500]
    build = batch([lo + s for s in slots])
    probe = [lo + s + d for s in slots for d in (-1, 0, 1)]
    return build, batch(probe), lo, tsize


def _null_oob_dead():
    lo, tsize = 0, 1 << 8
    build = batch([3, 4, 200, 255])
    probe = batch([3, 4, 200, 255, -5, 256, 10_000, 3, 4, 200],
                  live=[1, 1, 1, 1, 1, 1, 1, 0, 0, 1],
                  nulls=[0, 0, 0, 0, 0, 0, 0, 0, 0, 1])
    return build, probe, lo, tsize


def _mostly_empty():
    rng = np.random.default_rng(4100)
    keys = rng.permutation(4000)[:3000]
    build = batch(keys, live=rng.random(3000) < 0.03)     # a filter's leavings
    probe = batch(rng.integers(-10, 4100, 20_000))
    base, tsize = canonical_direct_table(0, 3999)
    return build, probe, base, tsize


def _spec_keys():
    okeys = spec_key(np.arange(6000))
    lkeys = np.repeat(okeys, 4)                    # 4 lines a key, in order
    lkeys[::97] += 8                               # the gaps: no order there
    base, tsize = canonical_direct_table(int(okeys.min()), int(okeys.max()))
    build = batch(okeys, live=np.arange(6000) % 5 != 0)
    return build, batch(lkeys), base, tsize


def _tiny_table():
    return batch([1, 3, 6]), batch([0, 1, 2, 3, 6, 7, 8]), 0, 8


CASES = {"slot_edges": _edges, "null_oob_dead": _null_oob_dead,
         "mostly_empty_build": _mostly_empty, "spec_sparse_keys": _spec_keys,
         "tiny_table": _tiny_table}


@pytest.mark.parametrize("case", list(CASES))
def test_bit_table_probe_is_direct_probe(case):
    build, probe, lo, tsize = CASES[case]()
    ok, safe_bidx, dup = J.direct_probe(probe, build, KEY, KEY, lo, tsize,
                                        False, None, ())
    bok, table, slot, bdup = J.direct_bitmap_probe(probe, build, KEY, KEY,
                                                   lo, tsize, ())
    assert table.shape == (tsize,) and slot.dtype == jnp.int32
    assert not bool(dup) and not bool(bdup)
    np.testing.assert_array_equal(np.asarray(bok), np.asarray(ok))
    assert 0 < int(ok.sum()) < probe.capacity
    # the lazy join's rows: compacted to a width that holds the matches,
    # the row ids read after the compaction equal those read before it
    want = 1 << int(ok.sum()).bit_length()
    perm = K.compact_perm(ok)[:want]
    parent = jnp.clip(jnp.take(safe_bidx, perm), 0, build.capacity - 1)
    change = jnp.clip(jnp.take(table, jnp.take(slot, perm)), 0,
                      build.capacity - 1)
    np.testing.assert_array_equal(np.asarray(change), np.asarray(parent))
    np.testing.assert_array_equal(np.asarray(jnp.take(bok, perm)),
                                  np.asarray(jnp.take(ok, perm)))


@pytest.mark.parametrize("tsize,words", [(8, 1), (32, 1), (64, 2),
                                         (1 << 22, 1 << 17),
                                         (1 << 27, 1 << 22), (100, 4)])
def test_occupancy_words(tsize, words):
    """32 slots a word, a power of two of words: the bits of SF10's
    2^27-slot orders table are 2^22 words (16 MiB)."""
    assert J.occupancy_words(tsize) == words
    assert 32 * words >= tsize


def test_occupancy_bits_hold_every_slot_once():
    tsize = 1 << 12
    rng = np.random.default_rng(4101)
    table = np.where(rng.random(tsize) < 0.3, np.arange(tsize), -1)
    bits = np.asarray(J.occupancy_bits(jnp.asarray(table, jnp.int32)))
    words = J.occupancy_words(tsize)
    s = np.arange(tsize)
    got = (bits[s % words] >> (s // words)) & 1
    np.testing.assert_array_equal(got.astype(bool), table >= 0)
    assert bits.dtype == np.uint32 and bits.shape == (words,)


def test_duplicate_build_keys_still_raise_dup():
    build = batch([5, 9, 5, 11])
    probe = batch([5, 9, 11, 12])
    _, _, dup = J.direct_probe(probe, build, KEY, KEY, 0, 16, False, None, ())
    _, _, _, bdup = J.direct_bitmap_probe(probe, build, KEY, KEY, 0, 16, ())
    assert bool(dup) and bool(bdup)


# --- through the fused compiler: which joins take the bit table ----------------

N_FACT, N_DIM = 4096, 1000


@pytest.fixture(scope="module")
def tables():
    rng = np.random.default_rng(4102)
    fk = np.where(np.arange(N_FACT) % 64 == 0,
                  rng.integers(1, N_DIM + 1, N_FACT), 0)
    fact = pa.table({"fk": pa.array(fk, type=pa.int64()),
                     "w": pa.array(rng.integers(0, 100, N_FACT),
                                   type=pa.int64())})
    dim = pa.table({"k": pa.array(np.arange(1, N_DIM + 1), type=pa.int64()),
                    "v": pa.array(rng.integers(0, 100, N_DIM),
                                  type=pa.int64())})
    return fact, dim


def _oracle(fact, dim, on) -> tuple:
    j = fact.to_pandas().merge(dim.to_pandas(), left_on="fk", right_on="k")
    if on == "residual":
        j = j[j.w < j.v]
    elif on == "two_keys":
        j = j[(j.w % 7) == (j.v % 7)]
    return int((j.w * 1000 + j.v).sum()), len(j)


ON = {"one_key": "fk = k",
      "two_keys": "fk = k AND w % 7 = v % 7",
      "residual": "fk = k AND w < v"}


@pytest.mark.parametrize("on,bitmap", [("one_key", 1), ("two_keys", 0),
                                       ("residual", 0)])
def test_only_a_single_key_join_without_residual_reads_the_bits(tables, on,
                                                                bitmap):
    """The second execution adopts the join's hint (64 of 4096 probe rows
    match: the lazy join); a single-key join then probes the bit table,
    once per plan walk, and the others keep direct_probe. The answer is the
    reference's on every execution."""
    fact, dim = tables
    sql = (f"SELECT sum(w * 1000 + v) AS s, count(*) AS c "
           f"FROM fact JOIN dim ON {ON[on]}")
    e = QueryEngine()
    e.hint_store = None
    e.register_table("fact", fact)
    e.register_table("dim", dim)
    moved = []
    for _ in range(3):
        e.result_cache.clear()
        with tracing.counter_delta() as d:
            t = e.execute(sql)
        assert (t.column("s")[0].as_py(), t.column("c")[0].as_py()) == \
            _oracle(fact, dim, on)
        assert d.get("join.direct_routes") == 1 and d.get("fused.execute")
        assert not d.get("fused.compact_repair")
        moved.append(d.get("join.bitmap_probes", 0))
    assert moved == [0, bitmap, bitmap]


def test_lazy_join_rows_equal_the_reference(tables):
    """Every joined row, not an aggregate of them, under the adopted hint."""
    fact, dim = tables
    sql = ("SELECT fk, w, v FROM fact JOIN dim ON fk = k "
           "ORDER BY fk, w, v")
    want = (fact.to_pandas().merge(dim.to_pandas(), left_on="fk",
                                   right_on="k")[["fk", "w", "v"]]
            .sort_values(["fk", "w", "v"]).reset_index(drop=True))
    e = QueryEngine()
    e.hint_store = None
    e.register_table("fact", fact)
    e.register_table("dim", dim)
    for run in range(2):
        e.result_cache.clear()
        with tracing.counter_delta() as d:
            got = e.execute(sql).to_pandas()
        assert d.get("join.bitmap_probes", 0) == run
        pd.testing.assert_frame_equal(got, want, check_dtype=False)
