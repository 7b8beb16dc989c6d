"""Distributed out-of-core tests (docs/out_of_core.md): a REAL 2-worker
in-process cluster under a tiny admission HBM budget, proving oversized
joins run as per-bucket GRACE fragments spread across BOTH workers with
row-identical results, that the `IGLOO_GRACE_DISTRIBUTED=0` kill switch
restores the bit-identical single-node demoted ladder, and that shapes the
distributed planner rejects still complete through that ladder.

TPC-H-shaped inputs come from the bench generator at a tiny scale factor so
the queries are the real q3/q5/q18 texts; the admission budget is scaled to
the same ~1/8-of-working-set ratio the memory-scaled bench gate proves.
Worker-death re-dispatch rides in the slow tier.
"""
import time

import pytest

from igloo_tpu.bench.tpch import QUERIES, gen_tables
from igloo_tpu.catalog import MemTable
from igloo_tpu.cluster import serving
from igloo_tpu.cluster.client import DistributedClient
from igloo_tpu.cluster.coordinator import CoordinatorServer
from igloo_tpu.cluster.worker import Worker
from igloo_tpu.engine import QueryEngine

BUDGET = 1 << 18  # ~1/8 of the sf=0.002 lineitem working set


def _assert_same(got, want):
    import pandas as pd
    pd.testing.assert_frame_equal(got.to_pandas().reset_index(drop=True),
                                  want.to_pandas().reset_index(drop=True),
                                  check_dtype=False, atol=1e-6)


@pytest.fixture(scope="module")
def cluster():
    tables = gen_tables(sf=0.002)
    local = QueryEngine(use_jit=True)
    for n, t in tables.items():
        local.register_table(
            n, MemTable(t, partitions=4 if t.num_rows > 1000 else 1))
    coord = CoordinatorServer("grpc+tcp://127.0.0.1:0", worker_timeout_s=60.0,
                              use_jit=True)
    # every query predicting past this budget demotes; the coordinator then
    # tries the distributed out-of-core plan before the single-node ladder
    coord.admission = serving.AdmissionController(hbm_budget_bytes=BUDGET)
    caddr = f"127.0.0.1:{coord.port}"
    workers = [Worker(caddr, port=0, heartbeat_interval_s=0.5, use_jit=True)
               for _ in range(2)]
    for w in workers:
        w.start()
    deadline = time.time() + 20
    while len(coord.membership.live()) < 2 and time.time() < deadline:
        time.sleep(0.05)
    assert len(coord.membership.live()) == 2
    for n, t in tables.items():
        coord.register_table(
            n, MemTable(t, partitions=4 if t.num_rows > 1000 else 1))
    try:
        yield {"coord": coord, "addr": caddr, "workers": workers,
               "local": local}
    finally:
        for w in workers:
            w.shutdown()
        coord.shutdown()


def _run(cluster, sql, fresh=False):
    if fresh:
        # two adaptive layers would silently skip the path under test: the
        # plan-keyed result cache replays a prior run's result (and metrics)
        # without executing, and carrier ratios measured by any earlier
        # demoted run shrink the lane-byte estimates until the plan prices
        # UNDER the grace budget (codec.carrier_ratio) — correct adaptive
        # behavior, but these tests assert the cold-state oversized route
        from igloo_tpu.exec import codec
        cluster["coord"].engine.result_cache.clear()
        codec.reset_carrier_ratios()
    client = DistributedClient(cluster["addr"])
    got = client.execute(sql)
    m = client.last_metrics()
    client.close()
    return got, m


def test_q3_shape_grace_partitions_on_both_workers(cluster):
    """THE acceptance check: an over-budget q3-shaped join-aggregate runs
    as per-bucket GRACE join fragments on BOTH workers, row-identical to
    the local engine, with the oversized block attributing the plan."""
    got, m = _run(cluster, QUERIES["q3"], fresh=True)
    _assert_same(got, cluster["local"].execute(QUERIES["q3"]))
    ov = m.get("oversized")
    assert ov, f"query did not take the distributed out-of-core path: {m}"
    # the coordinator floors tiny admission budgets (partition counts must
    # stay sane), so >= not ==
    assert ov["budget_bytes"] >= BUDGET
    assert ov["buckets"] >= 2
    assert ov["partitioned_leaves"] >= 2  # orders AND lineitem bucketed
    joins = [f for f in m["fragments"] if f.get("kind") == "join"]
    assert len(joins) == ov["buckets"]
    # GRACE partitions (the buckets) landed on BOTH workers
    assert len({f["worker"] for f in joins}) == 2
    # exchange fragments hash-partitioned their side into the buckets
    exchanges = [f for f in m["fragments"] if f.get("kind") == "exchange"]
    assert exchanges
    assert all(f.get("buckets") == ov["buckets"] for f in exchanges)


def test_q5_shape_replicates_small_dims(cluster):
    """q5's six-table join: big sides bucketed, small dimension tables
    (nation/region/supplier/customer at this scale) replicated whole."""
    got, m = _run(cluster, QUERIES["q5"], fresh=True)
    _assert_same(got, cluster["local"].execute(QUERIES["q5"]))
    ov = m.get("oversized")
    assert ov and ov["buckets"] >= 2
    assert ov["partitioned_leaves"] >= 2
    assert ov["replicated_leaves"] >= 1
    joins = [f for f in m["fragments"] if f.get("kind") == "join"]
    assert len({f["worker"] for f in joins}) == 2


def test_q18_shape_completes_through_fallback(cluster):
    """q18's IN-subquery join tree does not qualify for the distributed
    plan — it must still complete, row-identical, through the single-node
    demoted ladder (the silent-fallback contract)."""
    got, m = _run(cluster, QUERIES["q18"])
    _assert_same(got, cluster["local"].execute(QUERIES["q18"]))


def test_kill_switch_bit_identical(cluster, monkeypatch):
    """IGLOO_GRACE_DISTRIBUTED=0: the oversized path never engages and the
    single-node demoted ladder answers bit-identically."""
    want, base = _run(cluster, QUERIES["q3"], fresh=True)
    monkeypatch.setenv("IGLOO_GRACE_DISTRIBUTED", "0")
    got, m = _run(cluster, QUERIES["q3"], fresh=True)
    monkeypatch.delenv("IGLOO_GRACE_DISTRIBUTED")
    assert base.get("oversized")
    assert not m.get("oversized")
    _assert_same(got, want)
    _assert_same(got, cluster["local"].execute(QUERIES["q3"]))


def test_worker_streaming_exchange_counters(cluster):
    """The worker half of the tentpole is observable: scan pieces were
    hash-routed through streaming puts (exchange.stream_chunks) and GRACE
    bucket spread is attributed (grace.remote_partitions coordinator-side)."""
    from igloo_tpu.cluster.rpc import flight_action_raw
    _run(cluster, QUERIES["q3"], fresh=True)
    streamed = 0
    for w in cluster["workers"]:
        text = flight_action_raw(w.address, "metrics").decode()
        for line in text.splitlines():
            if line.startswith("igloo_exchange_stream_chunks_total"):
                streamed += float(line.split()[-1])
    assert streamed > 0
    ctext = flight_action_raw(cluster["addr"], "metrics").decode()
    assert "igloo_grace_remote_partitions_total" in ctext


def test_worker_death_redispatches_oversized(cluster):
    """Kill a worker that joined after sync: the oversized query either
    re-plans over the survivors or falls back to the single-node ladder —
    both must answer row-identically."""
    coord = cluster["coord"]
    extra = Worker(cluster["addr"], port=0, heartbeat_interval_s=0.5,
                   use_jit=True)
    extra.start()
    deadline = time.time() + 10
    while len(coord.membership.live()) < 3 and time.time() < deadline:
        time.sleep(0.05)
    assert len(coord.membership.live()) == 3
    extra.shutdown()  # silent death, no deregistration
    # wait until the port is actually dark: an in-process shutdown can leave
    # the Flight socket accepting for a moment, and a successful table sync
    # would keep the corpse in the placement
    from igloo_tpu.cluster.rpc import flight_action_raw
    deadline = time.time() + 10
    while time.time() < deadline:
        try:
            flight_action_raw(extra.address, "metrics")
            time.sleep(0.1)
        except Exception:
            break
    got, m = _run(cluster, QUERIES["q3"], fresh=True)
    _assert_same(got, cluster["local"].execute(QUERIES["q3"]))
    assert all(w.addr != extra.address for w in coord.membership.live())
