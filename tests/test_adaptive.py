"""Adaptive execution tests (docs/adaptive.md): the AdaptiveStats store's
round-trip/merge/staleness contract, salted partitioning correctness, the
greedy join-reorder pass (estimates first, observations flip the order), the
q9/q18-shaped reorder equivalence, and a real 2-worker in-process cluster
exercising the broadcast switch, hot-key salting, the IGLOO_ADAPTIVE=0 kill
switch, and the "stale stats mis-route but never corrupt" safety contract.

Everything runs eager (use_jit=False) on tiny tables — the decisions under
test are PLAN-level, so nothing here needs a compile; tier-1 is near its
time budget.
"""
import time

import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

from igloo_tpu.catalog import MemTable
from igloo_tpu.cluster import exchange
from igloo_tpu.cluster.client import DistributedClient
from igloo_tpu.cluster.coordinator import CoordinatorServer
from igloo_tpu.cluster.worker import Worker
from igloo_tpu.engine import QueryEngine
from igloo_tpu.exec import hints
from igloo_tpu.parallel.shuffle import pathological_share
from igloo_tpu.plan import logical as L
from igloo_tpu.utils import tracing


def _sorted_frame(t: pa.Table) -> pd.DataFrame:
    df = t.to_pandas()
    return df.sort_values(list(df.columns)).reset_index(drop=True)


def _assert_same(got: pa.Table, want: pa.Table):
    pd.testing.assert_frame_equal(_sorted_frame(got), _sorted_frame(want),
                                  check_dtype=False, atol=1e-9)


# --- AdaptiveStats store (exec/hints.py) ------------------------------------


KEY = ("join", "inner", "k",
       ("scan", "t", "()", None), ("scan", "u", "()", None))


def test_store_roundtrip_merge_and_remove(tmp_path):
    path = str(tmp_path / "stats.json")
    s = hints.AdaptiveStats(path)
    s.observe(KEY, rows=100, bytes=2048)
    s.observe(KEY, max_share=0.9, hot_bucket=1, nbuckets=2)  # merges
    s.observe(KEY, bogus_field=5)                            # dropped
    s.flush()
    s2 = hints.AdaptiveStats(path)
    assert s2.observed(KEY) == {"rows": 100, "bytes": 2048, "max_share": 0.9,
                                "hot_bucket": 1, "nbuckets": 2}
    assert s2.observed_rows(KEY) == 100
    s2.observe(KEY, rows=40, in_rows=200)   # last observation wins
    assert s2.selectivity(KEY) == pytest.approx(0.2)
    s2.remove(KEY)
    s2.flush()
    assert hints.AdaptiveStats(path).observed(KEY) is None


def test_store_survives_corrupt_file_and_junk_records(tmp_path):
    path = str(tmp_path / "stats.json")
    path2 = str(tmp_path / "stats2.json")
    with open(path, "w") as f:
        f.write("{not json")
    assert hints.AdaptiveStats(path).observed(KEY) is None  # no raise
    # junk values inside a valid file: non-dict records and unknown fields
    # are dropped by _coerce, known fields survive
    import hashlib
    import json
    d = hashlib.sha1(repr(KEY).encode()).hexdigest()
    with open(path2, "w") as f:
        json.dump({d: {"rows": 7, "wat": 1}, "other": 3}, f)
    s = hints.AdaptiveStats(path2)
    assert s.observed(KEY) == {"rows": 7}


def test_plan_fp_shapes():
    eng = QueryEngine(use_jit=False)
    eng.register_table("t", MemTable(pa.table({"a": [1, 2, 3]})))
    eng.register_table("u", MemTable(pa.table({"b": [1, 2]})))
    jp = eng.plan("SELECT a FROM t JOIN u ON t.a = u.b")
    fps = [hints.plan_fp(n) for n in L.walk_plan(jp)]
    assert any(fp is not None for fp in fps)
    # ORDER BY keys stably (watchtower baselines would otherwise skip
    # nearly every production query); direction flips the key
    sp = eng.plan("SELECT a FROM t ORDER BY a")
    assert hints.plan_fp(sp) is not None
    assert hints.plan_fp(sp) == hints.plan_fp(eng.plan(
        "SELECT a FROM t ORDER BY a"))
    assert hints.plan_fp(sp) != hints.plan_fp(eng.plan(
        "SELECT a FROM t ORDER BY a DESC"))
    # truly unhandled root shapes (set ops) still have no stable key
    up = eng.plan("SELECT a FROM t UNION ALL SELECT b AS a FROM u")
    assert hints.plan_fp(up) is None
    fp = next(fp for fp in fps if fp is not None)
    assert hints.digest_key(fp) == hints.digest_key(fp)


def test_pathological_share_bound():
    assert pathological_share(8) == pytest.approx(0.5)   # 4x uniform
    assert pathological_share(2) == pytest.approx(0.75)  # capped
    assert pathological_share(1) == pytest.approx(0.75)


# --- salted partitioning (cluster/exchange.py) ------------------------------


def _skewed(n=1200, hot=7, share=0.8, seed=3):
    rng = np.random.default_rng(seed)
    keys = np.where(rng.random(n) < share, hot,
                    rng.integers(0, 40, n)).astype(np.int64)
    return pa.table({"k": keys, "v": np.arange(n, dtype=np.int64)})


def test_salted_partition_probe_spreads_hot_bucket():
    t = _skewed()
    B, S = 4, 3
    plain = exchange.partition_table(t, [0], B)
    counts = [p.num_rows for p in plain]
    hot = int(np.argmax(counts))
    slices, base = exchange.salted_partition(t, [0], B, (hot, S, "probe"))
    assert len(slices) == B + S - 1
    # base counts describe the UNSALTED distribution (the skew signal)
    assert list(base) == counts
    # every row lands in exactly one bucket
    assert sum(s.num_rows for s in slices) == t.num_rows
    got = sorted(v for s in slices for v in s.column("v").to_pylist())
    assert got == t.column("v").to_pylist()
    # non-hot buckets untouched; hot rows spread ~evenly over {hot}+extras
    for b in range(B):
        if b != hot:
            assert slices[b].num_rows == counts[b]
    spread = [slices[hot].num_rows] + \
        [slices[B + j].num_rows for j in range(S - 1)]
    assert sum(spread) == counts[hot]
    assert max(spread) - min(spread) <= 1


def test_salted_partition_build_replicates_hot_bucket():
    t = _skewed()
    B, S = 4, 3
    plain = exchange.partition_table(t, [0], B)
    counts = [p.num_rows for p in plain]
    hot = int(np.argmax(counts))
    slices, base = exchange.salted_partition(t, [0], B, (hot, S, "build"))
    assert list(base) == counts
    hot_vs = sorted(plain[hot].column("v").to_pylist())
    # hot bucket stays in place AND each extra bucket holds a full copy
    assert sorted(slices[hot].column("v").to_pylist()) == hot_vs
    for j in range(S - 1):
        assert sorted(slices[B + j].column("v").to_pylist()) == hot_vs
    assert sum(s.num_rows for s in slices) == t.num_rows + (S - 1) * counts[hot]


# --- greedy join reorder (plan/optimizer.py) --------------------------------


REORDER_SQL = (
    "SELECT b.b_v, s.s_v, m.m_k FROM big b "
    "JOIN (SELECT m_k FROM midraw GROUP BY m_k) m ON b.b_k = m.m_k "
    "JOIN small s ON b.b_s = s.s_id")


def _reorder_engine() -> QueryEngine:
    rng = np.random.default_rng(5)
    eng = QueryEngine(use_jit=False)
    eng.register_table("big", MemTable(pa.table({
        "b_k": rng.integers(0, 5, 800),
        "b_s": rng.integers(0, 30, 800),
        "b_v": np.arange(800, dtype=np.int64)})))
    eng.register_table("midraw", MemTable(pa.table({
        "m_k": rng.integers(0, 5, 600)})))
    eng.register_table("small", MemTable(pa.table({
        "s_id": np.arange(30, dtype=np.int64),
        "s_v": rng.integers(0, 100, 30)})))
    return eng


def _leftmost_table(plan: L.LogicalPlan) -> str:
    """Table of the spine's first build relation (left-most leaf scan)."""
    while not isinstance(plan, L.Scan):
        plan = plan.left if isinstance(plan, L.Join) else plan.input
    return plan.table


def test_reorder_greedy_then_observed_flip(monkeypatch):
    eng = _reorder_engine()
    # kill switch: written order, bit-identical to the pre-adaptive planner
    monkeypatch.setenv(hints.ADAPTIVE_ENV, "0")
    p0 = eng.plan(REORDER_SQL)
    assert _leftmost_table(p0) == "big"          # written order stands
    want = eng.execute(REORDER_SQL)
    monkeypatch.delenv(hints.ADAPTIVE_ENV)

    # no observations: greedy by estimated scan bytes -> `small` first
    c0 = tracing.counters()
    p1 = eng.plan(REORDER_SQL)
    assert _leftmost_table(p1) == "small"
    c1 = tracing.counters()
    assert c1.get("adaptive.reorder", 0) > c0.get("adaptive.reorder", 0)
    eng.result_cache = type(eng.result_cache)()
    _assert_same(eng.execute(REORDER_SQL), want)

    # observations: the aggregated subtree is 5 rows, far under `small`'s
    # estimate -> the order flips to the derived relation first
    store = hints.adaptive_store()
    for node in L.walk_plan(p0):
        fp = hints.plan_fp(node)
        scans = {n.table for n in L.walk_plan(node) if isinstance(n, L.Scan)}
        if fp is not None and scans == {"midraw"}:
            store.observe(fp, rows=5)
    p2 = eng.plan(REORDER_SQL)
    assert _leftmost_table(p2) == "midraw"
    eng.result_cache = type(eng.result_cache)()
    _assert_same(eng.execute(REORDER_SQL), want)


@pytest.mark.slow  # 61.8-76.9 s alone on the CPU (PR 32)
def test_q9_q18_reorder_equivalence(monkeypatch):
    """The acceptance shape: q9 (6-table chain) and q18 (chain above a semi
    join) produce identical results with the adaptive loop off, on its first
    (estimate-driven) run, and on a second run planned from the first run's
    observations. Slow tier: six eager TPC-H runs are ~30s of pure op
    overhead; the crafted-spine test above covers the reorder logic fast."""
    from igloo_tpu.bench.tpch import QUERIES, gen_tables, register_all
    tables = gen_tables(sf=0.001, seed=7)
    eng_off = QueryEngine(use_jit=False)
    eng_on = QueryEngine(use_jit=False)
    register_all(eng_off, tables)
    register_all(eng_on, tables)
    for q in ("q9", "q18"):
        with monkeypatch.context() as m:
            m.setenv(hints.ADAPTIVE_ENV, "0")
            want = eng_off.execute(QUERIES[q])
        first = eng_on.execute(QUERIES[q])       # estimates (+ records)
        _assert_same(first, want)
        eng_on.result_cache = type(eng_on.result_cache)()
        second = eng_on.execute(QUERIES[q])      # planned from observations
        _assert_same(second, want)


# --- the 2-worker cluster: broadcast switch, salting, staleness -------------


BCAST_SQL = ("SELECT o.o_id, o.o_total, c.c_name FROM orders o "
             "JOIN cust c ON o.o_cust = c.c_id")
SALT_SQL = ("SELECT h.h_key, h.h_val, w.w_pad FROM horders h "
            "LEFT JOIN wcust w ON h.h_key = w.w_id")


@pytest.fixture(scope="module")
def cluster():
    rng = np.random.default_rng(9)
    orders = pa.table({"o_id": np.arange(600, dtype=np.int64),
                       "o_cust": rng.integers(0, 50, 600),
                       "o_total": np.round(rng.random(600) * 100, 2)})
    cust = pa.table({"c_id": np.arange(50, dtype=np.int64),
                     "c_name": pa.array([f"c{i:03d}" for i in range(50)])})
    # hot probe (90% of rows on one key -> one bucket far past the B=2
    # pathological bound of 0.75) against a build side that is SHORT in rows
    # but WIDE in bytes, so the broadcast switch correctly declines and the
    # exchange — the thing salting fixes — stays in play. The pads must be
    # DISTINCT per row: observed sizes are carrier bytes now, and a repeated
    # pad collapses to one dictionary value — wide enough to decline
    # broadcast at seed, ~4KB encoded, and broadcast would (correctly) win
    hkeys = np.where(rng.random(2500) < 0.9, 7,
                     rng.integers(0, 60, 2500)).astype(np.int64)
    horders = pa.table({"h_key": hkeys,
                        "h_val": rng.integers(0, 1000, 2500)})
    wcust = pa.table({"w_id": np.arange(60, dtype=np.int64),
                      "w_pad": pa.array([f"{i:04d}" * 1024
                                         for i in range(60)])})
    coord = CoordinatorServer("grpc+tcp://127.0.0.1:0", worker_timeout_s=60.0,
                              use_jit=False)
    caddr = f"127.0.0.1:{coord.port}"
    workers = [Worker(caddr, port=0, heartbeat_interval_s=0.5, use_jit=False)
               for _ in range(2)]
    for w in workers:
        w.start()
    deadline = time.time() + 20
    while len(coord.membership.live()) < 2 and time.time() < deadline:
        time.sleep(0.05)
    for name, t in (("orders", orders), ("cust", cust),
                    ("horders", horders), ("wcust", wcust)):
        coord.register_table(name, MemTable(t))
    # local oracle results computed ONCE here: the local engine harvests
    # observations under the same fingerprints the cluster planner reads,
    # and the per-test store reset must wipe them before any test plans
    local = QueryEngine(use_jit=False)
    for name, t in (("orders", orders), ("cust", cust),
                    ("horders", horders), ("wcust", wcust)):
        local.register_table(name, MemTable(t))
    want = {sql: local.execute(sql) for sql in (BCAST_SQL, SALT_SQL)}
    try:
        yield {"addr": caddr, "want": want}
    finally:
        for w in workers:
            w.shutdown()
        coord.shutdown()


def test_cluster_broadcast_switch(cluster, monkeypatch):
    client = DistributedClient(cluster["addr"])
    want = cluster["want"][BCAST_SQL]
    # run 1: no observations -> plain exchange (and the sizes get recorded)
    got1 = client.execute(BCAST_SQL)
    m1 = client.last_metrics()
    _assert_same(got1, want)
    assert any(d.get("strategy") == "shuffle" for d in m1["adaptive"]), \
        m1["adaptive"]
    # run 2: observed build side is tiny -> broadcast replaces the exchange
    c0 = tracing.counters()
    got2 = client.execute(BCAST_SQL)
    m2 = client.last_metrics()
    _assert_same(got2, want)
    dec = [d for d in m2["adaptive"] if d.get("strategy") == "broadcast"]
    assert dec and dec[0]["adaptive_source"] == "observed", m2["adaptive"]
    assert dec[0]["build"] == "right"            # cust is the small side
    assert not any(f.get("kind") == "exchange" for f in m2["fragments"])
    assert tracing.counters().get("adaptive.broadcast", 0) > \
        c0.get("adaptive.broadcast", 0)
    # kill switch on the SAME warmed cluster reproduces the old plan
    monkeypatch.setenv(hints.ADAPTIVE_ENV, "0")
    got3 = client.execute(BCAST_SQL)
    m3 = client.last_metrics()
    _assert_same(got3, want)
    assert m3["adaptive"] == []
    assert any(f.get("kind") == "exchange" for f in m3["fragments"])
    client.close()


def test_cluster_hot_key_salting(cluster):
    client = DistributedClient(cluster["addr"])
    want = cluster["want"][SALT_SQL]
    got1 = client.execute(SALT_SQL)
    m1 = client.last_metrics()
    _assert_same(got1, want)
    assert any(d.get("strategy") == "shuffle" for d in m1["adaptive"])
    c0 = tracing.counters()
    got2 = client.execute(SALT_SQL)
    m2 = client.last_metrics()
    _assert_same(got2, want)
    dec = [d for d in m2["adaptive"] if d.get("strategy") == "salted"]
    assert dec and dec[0]["max_share"] > 0.75, m2["adaptive"]
    c1 = tracing.counters()
    assert c1.get("adaptive.salted", 0) > c0.get("adaptive.salted", 0)
    assert c1.get("exchange.salted", 0) > c0.get("exchange.salted", 0)
    # the hot bucket's work spread across BOTH workers: the salted extra
    # bucket landed on a different worker than the hot bucket's own fragment
    hot, nb = dec[0]["hot_bucket"], dec[0]["buckets"]
    joins = [f for f in m2["fragments"] if f.get("kind") == "join"]
    hot_workers = {f["worker"] for f in joins
                   if f.get("bucket") == hot or f.get("bucket", -1) >= nb}
    assert len(hot_workers) == 2, joins
    client.close()


def test_cluster_stale_sketch_misroutes_but_never_corrupts(cluster):
    """The safety contract (exec/hints.py): a WRONG skew sketch — here the
    hot bucket flagged as the cold one — picks a useless salt, and the
    result is still exactly right."""
    client = DistributedClient(cluster["addr"])
    want = cluster["want"][SALT_SQL]
    got1 = client.execute(SALT_SQL)
    m1 = client.last_metrics()
    _assert_same(got1, want)
    # corrupt the recorded sketch: flag the COLD bucket as pathologically hot
    store = hints.adaptive_store()
    probe_keys = {f["stats_key"] for f in m1["fragments"]
                  if f.get("kind") == "exchange" and f.get("stats_key")}
    assert probe_keys
    real = [d for d in m1["adaptive"] if d.get("strategy") == "shuffle"]
    assert real
    nb = real[0]["buckets"]
    for sk in probe_keys:
        store.observe_by_digest(sk, max_share=0.99, hot_bucket=0,
                                nbuckets=nb)
    got2 = client.execute(SALT_SQL)
    m2 = client.last_metrics()
    assert any(d.get("strategy") == "salted" for d in m2["adaptive"]), \
        m2["adaptive"]
    _assert_same(got2, want)
    # a sketch taken at a DIFFERENT bucket count is not mappable: ignored
    hints.reset_adaptive_store()
    store = hints.adaptive_store()
    for sk in probe_keys:
        store.observe_by_digest(sk, max_share=0.99, hot_bucket=0,
                                nbuckets=nb + 3)
    got3 = client.execute(SALT_SQL)
    m3 = client.last_metrics()
    assert not any(d.get("strategy") == "salted" for d in m3["adaptive"])
    _assert_same(got3, want)
    client.close()
