"""How large a direct join's positional table may be comes from the chip's
memory (exec/cache.py direct_table_budget, exec/join.py direct_table_slots),
read by the fused compiler and the staged executor alike: a 2^27-slot table
— TPC-H Q3's order keys at SF10 as the spec spaces them — is taken on a
v5e, declined on a smaller device, and the fixed 2^24 slots stay where the
backend reports no limit (XLA:CPU). TPC-H q3 over the spec's sparse order
keys (benchmark/datagen_spec_keys.py) equals the benchmark's pandas
reference on both sides of the limit; SF1 q3's picks and program are the
same under the chip's limit as without one."""
import importlib.util
import os
import sys

import numpy as np
import pytest

from igloo_tpu import types as T
from igloo_tpu.engine import QueryEngine
from igloo_tpu.exec import cache
from igloo_tpu.exec import join as J
from igloo_tpu.exec.expr_compile import Compiled
from igloo_tpu.exec.fused import FusedCompiler
from igloo_tpu.sql.ast import JoinType
from igloo_tpu.utils import tracing
from test_hbm_budget import GB, fake_limits

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
V5E = 16_911_433_728            # a v5e's bytes_limit: 15.75 GiB


def bench_module(name: str):
    """benchmark/<name>.py by its path; its own imports (datagen_reads,
    datagen) are found beside it."""
    if BENCH not in sys.path:
        sys.path.append(BENCH)
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{name.replace('/', '_')}", os.path.join(BENCH, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def key(lo: int, hi: int) -> Compiled:
    return Compiled(fn=None, dtype=T.INT64, out_bounds=(lo, hi))


def pick(build_hi: int, build_cap: int, probe_cap: int = 1 << 26):
    """choose_direct_build over an FK join whose build side's key spans
    1..build_hi -> (its pick, the planning counters it bumped)."""
    with tracing.counter_delta() as d:
        got = J.choose_direct_build([key(1, build_hi)], [key(1, build_hi)],
                                    left_cap=probe_cap, right_cap=build_cap,
                                    join_type=JoinType.INNER)
    return got, {k: v for k, v in d.values().items()
                 if k.startswith("join.direct")}


# --- the rule -----------------------------------------------------------------

@pytest.mark.parametrize("limits,slots", [
    ((V5E,), V5E // 16 // 4),                 # a v5e: 2^27 fits, 2^28 not
    ((16 * GB,), (1 << 28)),                  # 16 GiB: exactly 2^28
    ((4 * GB,), (1 << 26)),
    ((16 * GB, 4 * GB), (1 << 26)),           # the tightest device decides
    ((None,), J.UNLIMITED_DIRECT_SLOTS),      # XLA:CPU: no stats at all
    (({},), J.UNLIMITED_DIRECT_SLOTS),        # stats without a limit
])
def test_the_limit_is_a_share_of_the_chip(monkeypatch, limits, slots):
    fake_limits(monkeypatch, *limits)
    assert J.direct_table_slots() == slots
    assert J.UNLIMITED_DIRECT_SLOTS == 1 << 24


def test_the_cpu_backend_keeps_2_24_slots():
    assert cache.direct_table_budget() is None
    assert J.direct_table_slots() == 1 << 24


@pytest.mark.parametrize("limits,taken", [
    ((V5E,), True),          # 2^27 x 4 B = 537 MB of a 1.06 GB share
    ((4 * GB,), False),      # a 256 MiB share: declined, counted
    ((None,), False),        # no limit: the fixed 2^24 slots
])
def test_sf10_sparse_order_keys_take_2_27_slots_on_a_v5e(monkeypatch, limits,
                                                         taken):
    fake_limits(monkeypatch, *limits)
    got, moved = pick(60_000_000, build_cap=1 << 24)
    if taken:
        assert got == ("right", (0, 1 << 27), 0)
        assert moved == {"join.direct_routes": 1,
                         "join.direct_table_bytes": (1 << 27) * 4}
    else:
        assert got is None
        assert moved == {"join.direct_ineligible": 1,
                         "join.direct_over_budget": 1}


@pytest.mark.parametrize("build_hi,tsize,over", [
    (150_000_000, 1 << 28, True),     # 2^28 slots: past a v5e's share
    (15_000_000, 1 << 25, False),     # SF10 with dense keys: fits
    (1_500_000, 1 << 22, False),      # SF1 orders
])
def test_a_v5e_takes_what_its_share_holds(monkeypatch, build_hi, tsize, over):
    fake_limits(monkeypatch, V5E)
    got, moved = pick(build_hi, build_cap=1 << 22)
    assert (got is None) == over
    assert bool(moved.get("join.direct_over_budget")) == over
    if not over:
        assert got[1] == (0, tsize)


def test_a_key_that_cannot_be_unique_is_no_budget_decline():
    """A side whose capacity exceeds its table is not a budget question."""
    got, moved = pick(1_000, build_cap=1 << 20, probe_cap=1 << 20)
    assert got is None
    assert moved == {"join.direct_ineligible": 1}


# --- SF1 q3: the picks of the parent ------------------------------------------

@pytest.mark.parametrize("limits", [(V5E,), (None,)])
@pytest.mark.parametrize("hi,cap,tsize", [
    (1_500_000, 1 << 22, 1 << 22),     # orders at SF1: o_orderkey 1..1.5 M
    (150_000, 1 << 18, 1 << 18),       # customer at SF1
])
def test_sf1_q3_picks_are_unchanged(monkeypatch, limits, hi, cap, tsize):
    fake_limits(monkeypatch, *limits)
    got, moved = pick(hi, build_cap=cap, probe_cap=1 << 23)
    assert got == ("right", (0, tsize), 0)
    assert moved["join.direct_table_bytes"] == tsize * 4


@pytest.fixture(scope="module")
def q3_sql() -> str:
    with open(os.path.join(BENCH, "queries", "q3.sql")) as f:
        return f.read()


def test_q3_program_key_does_not_read_the_limit(monkeypatch, q3_sql):
    """The program q3 compiles to under a v5e's limit is the one it compiles
    to without a limit, while the picks are the same: the limit decides a
    pick, and is in no key."""
    tables = bench_module("datagen").gen_tables(
        sf=0.01, seed=4000000101, tables=["customer", "orders", "lineitem"])

    def program_key():
        eng = QueryEngine()
        for name, tbl in tables.items():
            eng.register_table(name, tbl)
        with tracing.counter_delta() as d:
            _, key, _ = FusedCompiler(eng._executor()).compile(
                eng.plan(q3_sql))
        return key, d.get("join.direct_routes")
    free = program_key()
    monkeypatch.setattr(cache, "_bytes_limit", lambda: V5E)
    assert J.direct_table_slots() > 1 << 24
    assert program_key() == free
    assert free[1] == 2


# --- q3 over the spec's sparse order keys, against the reference --------------

@pytest.fixture(scope="module")
def sparse_sf002():
    """SF 0.02 of the tables q3 reads, order keys as the spec spaces them
    (1..120,000: a 2^18-slot table), and the benchmark's reference."""
    gen = bench_module("datagen_spec_keys")
    compare = bench_module("compare")
    oracle = bench_module("oracle/tpch_pandas")
    tables = gen.gen_tables(sf=0.02, seed=4000000103,
                            tables=["customer", "orders", "lineitem"])
    want = oracle.q3({n: compare.frame(t) for n, t in tables.items()})
    return tables, want, compare


@pytest.mark.parametrize("bytes_limit,direct", [
    (32 << 20, 2),     # a 2 MiB table budget: both joins positional
    (8 << 20, 1),      # 512 KiB: the orders join declined, sorted probe
])
def test_sparse_key_q3_equals_the_reference(monkeypatch, q3_sql, sparse_sf002,
                                            bytes_limit, direct):
    tables, want, compare = sparse_sf002
    monkeypatch.setattr(cache, "_bytes_limit", lambda: bytes_limit)
    # the scan and routing budgets as on the CPU: only the table's limit
    # is the device's here
    eng = QueryEngine(cache_budget_bytes=1 << 30, chunk_budget_bytes=2 << 30)
    for name, tbl in tables.items():
        eng.register_table(name, tbl)
    with tracing.counter_delta() as d:
        res = eng.query(q3_sql)
    assert res.stats.tier == "device"
    assert d.get("fused.execute") == 1 and "fused.unsupported" not in d
    assert d.get("join.direct_routes") == direct
    assert d.get("join.direct_over_budget", 0) == 2 - direct
    orders_table = (1 << 18) * 4 if direct == 2 else 0
    assert d.get("join.direct_table_bytes") == orders_table + 4096 * 4
    err, wrong, why = compare.compare(res.table, want)
    assert wrong == 0, why
    assert err <= 1e-9
    assert len(want) == 10


# --- the spec's order-key population ------------------------------------------

@pytest.fixture(scope="module")
def spec_keys():
    return bench_module("datagen_spec_keys")


def test_sparse_keys_use_8_of_every_32_values(spec_keys):
    i = np.arange(1, 1 << 16)
    k = spec_keys.sparse_key(i)
    assert (np.diff(k) > 0).all()                       # unique, in order
    assert set(np.unique(k % 32)) == set(range(8))      # low 3 bits kept
    blocks = np.bincount(k // 32)
    assert (blocks[1:-1] == 8).all() and blocks[0] == 7     # key 0 unused
    # SF10: 15 M orders end at 4 x 15 M, the spec's [1, SF x 1.5 M x 4]
    assert spec_keys.sparse_key(15_000_000) == 60_000_000
    assert spec_keys.sparse_key(1) == 1


def test_spec_keys_are_datagen_reads_but_for_the_order_keys(spec_keys):
    reads = bench_module("datagen_reads")
    names = ["customer", "orders", "lineitem"]
    got = spec_keys.gen_tables(sf=0.01, seed=4000000107, tables=names)
    base = reads.gen_tables(sf=0.01, seed=4000000107, tables=names)
    for name in names:
        assert got[name].column_names == base[name].column_names
        for col in got[name].column_names:
            a, b = got[name].column(col), base[name].column(col)
            if col in ("o_orderkey", "l_orderkey"):
                assert a.to_numpy().tolist() == spec_keys.sparse_key(
                    b.to_numpy()).tolist()
            else:
                assert a.equals(b), (name, col)
    okeys = got["orders"].column("o_orderkey").to_numpy()
    lkeys = got["lineitem"].column("l_orderkey").to_numpy()
    assert len(np.unique(okeys)) == len(okeys) == 15_000
    assert np.isin(lkeys, okeys).all()
    assert okeys.max() == spec_keys.sparse_key(15_000) == 60_000
