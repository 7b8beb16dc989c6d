"""Distributed control-plane tests: real coordinator + workers over localhost
Flight, real plan serde, elastic recovery. The reference has none of this —
its distributed path cannot even connect (SURVEY.md gaps G1/G2, §4: "no
distributed test, no multi-process test").
"""
import time

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from igloo_tpu.cluster.client import DistributedClient
from igloo_tpu.cluster.coordinator import CoordinatorServer
from igloo_tpu.cluster.worker import Worker
from igloo_tpu.engine import QueryEngine
from igloo_tpu.errors import IglooError


def _make_data(tmp_path):
    rng = np.random.default_rng(11)
    n = 5000
    orders = pa.table({
        "o_id": np.arange(n, dtype=np.int64),
        "o_cust": rng.integers(0, 200, n),
        "o_total": np.round(rng.random(n) * 1000, 2),
        "o_status": pa.array([["open", "shipped", "done"][i % 3]
                              for i in range(n)]),
    })
    cust = pa.table({
        "c_id": np.arange(200, dtype=np.int64),
        "c_name": pa.array([f"cust{i:03d}" for i in range(200)]),
        "c_tier": pa.array([["gold", "silver"][i % 2] for i in range(200)]),
    })
    po = tmp_path / "orders.parquet"
    pc = tmp_path / "cust.parquet"
    # several row groups so scans have partitions to stride
    pq.write_table(orders, po, row_group_size=1000)
    pq.write_table(cust, pc)
    return str(po), str(pc), orders, cust


@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("cluster")
    po, pc, orders, cust = _make_data(tmp)
    coord = CoordinatorServer("grpc+tcp://127.0.0.1:0", worker_timeout_s=60.0)
    caddr = f"127.0.0.1:{coord.port}"
    workers = [Worker(caddr, port=0, heartbeat_interval_s=0.5)
               for _ in range(2)]
    for w in workers:
        w.start()
    from igloo_tpu.connectors.parquet import ParquetTable
    coord.register_table("orders", ParquetTable(po))
    coord.register_table("cust", ParquetTable(pc))
    local = QueryEngine()
    local.register_table("orders", ParquetTable(po))
    local.register_table("cust", ParquetTable(pc))
    try:
        yield {"coord": coord, "addr": caddr, "workers": workers,
               "local": local, "paths": (po, pc)}
    finally:
        for w in workers:
            w.shutdown()
        coord.shutdown()


def _assert_same(got: pa.Table, want: pa.Table):
    import pandas as pd
    pd.testing.assert_frame_equal(got.to_pandas().reset_index(drop=True),
                                  want.to_pandas().reset_index(drop=True),
                                  check_dtype=False, atol=1e-9)


# --- plan serde (the wire format the reference faked, G1) ---

@pytest.mark.parametrize("sql", [
    "SELECT o_status, COUNT(*) AS c, SUM(o_total) AS s, AVG(o_total) AS a "
    "FROM orders GROUP BY o_status ORDER BY o_status",
    "SELECT c.c_tier, SUM(o.o_total) AS rev FROM orders o "
    "JOIN cust c ON o.o_cust = c.c_id WHERE o.o_total > 100 "
    "GROUP BY c.c_tier ORDER BY rev DESC",
    "SELECT o_id, o_total FROM orders WHERE o_status = 'open' "
    "ORDER BY o_total DESC LIMIT 7",
    "SELECT DISTINCT o_status FROM orders ORDER BY o_status",
    "SELECT CASE WHEN o_total > 500 THEN 'big' ELSE 'small' END AS b, "
    "COUNT(*) AS c FROM orders GROUP BY 1 ORDER BY 1",
])
def test_plan_serde_roundtrip(cluster, sql):
    from igloo_tpu.cluster import serde
    from igloo_tpu.exec.executor import Executor
    local = cluster["local"]
    plan = local.plan(sql)
    j = serde.plan_to_json(plan)
    import json
    j2 = json.loads(json.dumps(j))  # must be pure JSON
    plan2 = serde.plan_from_json(j2, local.catalog)
    got = Executor().execute_to_arrow(plan2)
    _assert_same(got, local.execute(sql))


def test_ipc_roundtrip():
    from igloo_tpu.cluster import serde
    t = pa.table({"a": [1, 2, None], "b": ["x", None, "z"]})
    assert serde.table_from_ipc(serde.table_to_ipc(t)).equals(t)


# --- distributed execution over the wire ---

def test_cluster_membership(cluster):
    client = DistributedClient(cluster["addr"])
    status = client.cluster_status()
    assert len(status["workers"]) == 2
    assert "orders" in status["tables"] and "cust" in status["tables"]
    client.close()


@pytest.mark.parametrize("sql", [
    # partial-aggregate pushdown across workers
    "SELECT o_status, COUNT(*) AS c, SUM(o_total) AS s, AVG(o_total) AS a, "
    "MIN(o_total) AS mn, MAX(o_total) AS mx "
    "FROM orders GROUP BY o_status ORDER BY o_status",
    # global aggregate
    "SELECT COUNT(*) AS c, SUM(o_total) AS s FROM orders",
    # distributed join: scan fragments on workers, join + agg above
    "SELECT c.c_tier, SUM(o.o_total) AS rev, COUNT(*) AS n FROM orders o "
    "JOIN cust c ON o.o_cust = c.c_id GROUP BY c.c_tier ORDER BY c.c_tier",
    # filter + sort + limit end-to-end
    "SELECT o_id, o_total FROM orders WHERE o_status = 'shipped' "
    "AND o_total > 800 ORDER BY o_total DESC, o_id LIMIT 11",
])
def test_distributed_query_matches_local(cluster, sql):
    client = DistributedClient(cluster["addr"])
    got = client.execute(sql)
    _assert_same(got, cluster["local"].execute(sql))
    client.close()


def test_distributed_uses_fragments(cluster):
    """The distributed path must actually fragment (not fall back to local)."""
    from igloo_tpu.cluster.fragment import DistributedPlanner
    plan = cluster["local"].plan(
        "SELECT o_status, SUM(o_total) AS s FROM orders "
        "GROUP BY o_status ORDER BY o_status")
    frags = DistributedPlanner(["w1", "w2"]).plan(plan)
    # 2 workers x row-group partitions -> >= 2 partial fragments + root
    assert len(frags) >= 3
    workers = {f.worker for f in frags[:-1]}
    assert workers == {"w1", "w2"}
    # partial fragments feed the root through __frag_ scans
    assert frags[-1].deps


def test_query_metrics_surface(cluster):
    """The reference defines QueryComplete{total_rows, execution_time_ms} and
    never populates it (distributed.proto:66-69); ours is real."""
    client = DistributedClient(cluster["addr"])
    t = client.execute("SELECT o_status, COUNT(*) AS c FROM orders "
                       "GROUP BY o_status ORDER BY o_status")
    m = client.last_metrics()
    assert m["total_rows"] == t.num_rows
    assert m["execution_time_s"] > 0
    assert len(m["fragments"]) >= 2  # partials + merge
    for f in m["fragments"]:
        assert f["rows"] >= 0 and f["elapsed_s"] >= 0 and f["worker"]
        # ISSUE 3: per-fragment time attribution + transfer/compile deltas
        assert "dispatch_s" in f and f["dispatch_s"] >= 0
        assert "dep_fetch_s" in f and "jit_misses" in f
    assert "fetch_s" in m and "recover_s" in m
    client.close()


# --- a dependency scan is keyed by position, its data cached by id ---
# These statements read `cust`, one scan partition: one partial fragment and
# a merge fragment that is ONE fused program over its `__frag_<id>` scan,
# as on a one-worker deployment. (Over `orders` the merge unions one
# dependency per worker and runs staged, whose keys never held a name.)

def _run_uncached(cluster, client, sql):
    """Execute with the coordinator's result cache cleared, so the fragments
    run; return (answer, the fragments of last_metrics())."""
    cluster["coord"].engine.result_cache.clear()
    got = client.execute(sql)
    m = client.last_metrics()
    assert not m.get("result_cache_hit") and m["fragments"]
    return got, m["fragments"]


def _fused_programs(cluster) -> int:
    return sum(1 for w in cluster["workers"] for k in w.server._jit_cache
               if isinstance(k, tuple) and k and k[0] == "fused")


def _merge_fragments(frags: list) -> list:
    """The fragments that read other fragments' results."""
    return [f for f in frags if f.get("input_rows")]


@pytest.mark.parametrize("sql", [
    # q1-shaped: string group key, float sums, ORDER BY
    "SELECT c_tier, SUM(c_id * 0.5) AS h, AVG(c_id * 1.5) AS a, "
    "COUNT(*) AS c FROM cust WHERE c_id < 190 GROUP BY c_tier "
    "ORDER BY c_tier",
    # q6-shaped: one scalar aggregate
    "SELECT SUM(c_id * 0.5) AS rev FROM cust "
    "WHERE c_id > 10 AND c_id < 150 AND c_tier = 'gold'",
], ids=["q1_shaped", "q6_shaped"])
def test_merge_program_is_built_once(cluster, sql):
    """The merge fragment reads its dependency through a `__frag_<id>` scan
    and the id is new in every query: the program that merges it must not
    be (ISSUE 29)."""
    client = DistributedClient(cluster["addr"])
    want = cluster["local"].execute(sql)
    _run_uncached(cluster, client, sql)
    got, _ = _run_uncached(cluster, client, sql)
    _assert_same(got, want)
    programs = _fused_programs(cluster)
    got, frags = _run_uncached(cluster, client, sql)
    _assert_same(got, want)
    assert _merge_fragments(frags)
    assert [f["jit_misses"] for f in frags] == [0] * len(frags)
    # the leak is closed: a worker holds one program per plan, not per query
    assert _fused_programs(cluster) == programs
    client.close()


def test_shared_merge_program_never_aliases_data(cluster):
    """Two queries of one shape share the merge PROGRAM from B's first
    execution on; their dependency tables (other rows, another dictionary
    under the string key) stay apart."""
    shape = ("SELECT c_name, c_tier, SUM(c_id * 0.5) AS h, COUNT(*) AS c "
             "FROM cust WHERE {} GROUP BY c_name, c_tier ORDER BY c_name")
    a, b = shape.format("c_id < 5"), shape.format("c_id >= 195")
    local = cluster["local"]
    want_a, want_b = local.execute(a), local.execute(b)
    assert want_a.num_rows == want_b.num_rows == 5
    assert not set(want_a.column("c_name").to_pylist()) \
        & set(want_b.column("c_name").to_pylist())
    client = DistributedClient(cluster["addr"])
    for sql, want in ((a, want_a), (b, want_b), (a, want_a), (b, want_b)):
        got, frags = _run_uncached(cluster, client, sql)
        _assert_same(got, want)
        merge = _merge_fragments(frags)
        assert merge
        if sql is b:
            assert [f["jit_misses"] for f in merge] == [0] * len(merge)
    client.close()


def test_metrics_flight_action(cluster):
    """Both servers serve Prometheus text via the `metrics` action; the
    coordinator's includes worker-aggregated fragment stats."""
    from igloo_tpu.cluster.rpc import flight_action_raw
    client = DistributedClient(cluster["addr"])
    client.execute("SELECT o_status, COUNT(*) AS c FROM orders "
                   "GROUP BY o_status ORDER BY o_status")
    client.close()
    text = flight_action_raw(cluster["addr"], "metrics").decode()
    assert "igloo_workers_live 2" in text
    assert "# TYPE igloo_coordinator_worker_fragments_total counter" in text
    assert 'igloo_coordinator_worker_fragments_total{worker="' in text
    assert 'igloo_coordinator_worker_fragment_rows_total{worker="' in text
    assert "igloo_coordinator_distributed_queries_total" in text
    # worker-side registry, scraped directly from a worker
    waddr = cluster["workers"][0].address
    wtext = flight_action_raw(waddr, "metrics").decode()
    assert "igloo_worker_fragments_total" in wtext
    assert "igloo_jit_miss_total" in wtext


def test_client_schema_without_execution(cluster):
    client = DistributedClient(cluster["addr"])
    schema = client.schema("SELECT o_id, o_total FROM orders")
    assert schema.names == ["o_id", "o_total"]
    client.close()


def test_client_table_upload(cluster):
    client = DistributedClient(cluster["addr"])
    t = pa.table({"k": [1, 2, 3], "v": ["a", "b", "c"]})
    client.register_table("uploaded", t)
    got = client.execute("SELECT * FROM uploaded ORDER BY k")
    _assert_same(got, t)
    client.close()


def test_error_propagates(cluster):
    client = DistributedClient(cluster["addr"])
    with pytest.raises(IglooError, match="(?i)not found|unknown"):
        client.execute("SELECT * FROM no_such_table")
    client.close()


# --- full Flight surface (reference proto flight.proto:42-144) ---

def test_do_exchange_cmd_streams_query(cluster):
    import pyarrow.flight as flight
    client = flight.connect(f"grpc+tcp://{cluster['addr']}")
    desc = flight.FlightDescriptor.for_command(
        b"SELECT o_status, COUNT(*) AS c FROM orders GROUP BY o_status "
        b"ORDER BY o_status")
    writer, reader = client.do_exchange(desc)
    writer.done_writing()
    got = reader.read_all()
    want = cluster["local"].execute(
        "SELECT o_status, COUNT(*) AS c FROM orders GROUP BY o_status "
        "ORDER BY o_status")
    _assert_same(got, want)
    writer.close()
    client.close()


def test_do_exchange_path_roundtrip(cluster):
    """Upload batches through the exchange, get the stored table echoed."""
    import pyarrow.flight as flight
    client = flight.connect(f"grpc+tcp://{cluster['addr']}")
    t = pa.table({"x": [1, 2, 3], "s": ["p", "q", "r"]})
    desc = flight.FlightDescriptor.for_path("exchanged")
    writer, reader = client.do_exchange(desc)
    writer.begin(t.schema)
    for b in t.to_batches():
        writer.write_batch(b)
    writer.done_writing()
    got = reader.read_all()
    _assert_same(got, t)
    writer.close()
    client.close()
    # and the table is really registered
    dc = DistributedClient(cluster["addr"])
    _assert_same(dc.execute("SELECT * FROM exchanged ORDER BY x"), t)
    dc.close()


def test_do_exchange_path_writeless_echo(cluster):
    """A write-less path exchange (done_writing with no schema/batches) must
    echo the stored table — the one failure mode the narrowed upload handler
    is allowed to swallow (pyarrow's 'Client never sent a data message')."""
    import pyarrow.flight as flight
    client = flight.connect(f"grpc+tcp://{cluster['addr']}")
    desc = flight.FlightDescriptor.for_path("orders")
    writer, reader = client.do_exchange(desc)
    writer.done_writing()
    got = reader.read_all()
    want = cluster["local"].execute("SELECT * FROM orders")
    assert got.num_rows == want.num_rows
    assert set(got.schema.names) == set(want.schema.names)
    writer.close()
    client.close()


def test_poll_flight_info_action(cluster):
    import json as _json

    import pyarrow.flight as flight
    client = flight.connect(f"grpc+tcp://{cluster['addr']}")
    res = list(client.do_action(flight.Action(
        "poll_flight_info",
        _json.dumps({"sql": "SELECT o_id FROM orders"}).encode())))
    status = _json.loads(res[0].body.to_pybytes())
    assert status["complete"] and status["progress"] == 1.0
    info = flight.FlightInfo.deserialize(res[1].body.to_pybytes())
    assert info.schema.names == ["o_id"]
    client.close()


def test_handshake_token_auth(tmp_path, monkeypatch):
    """Stock-client handshake against a token-protected server; wrong token
    rejected, right token authenticates and calls succeed."""
    import pyarrow.flight as flight

    from igloo_tpu.cluster.coordinator import CoordinatorServer
    from igloo_tpu.cluster.rpc import TokenClientAuthHandler
    monkeypatch.setenv("IGLOO_TPU_AUTH_TOKEN", "sekrit")
    coord = CoordinatorServer("grpc+tcp://127.0.0.1:0", worker_timeout_s=60.0)
    try:
        addr = f"grpc+tcp://127.0.0.1:{coord.port}"
        bad = flight.connect(addr)
        with pytest.raises(flight.FlightUnauthenticatedError):
            bad.authenticate(TokenClientAuthHandler("wrong"))
        bad.close()
        ok = flight.connect(addr)
        ok.authenticate(TokenClientAuthHandler("sekrit"))
        actions = {a.type for a in ok.list_actions()}
        assert "poll_flight_info" in actions
        ok.close()
    finally:
        coord.shutdown()


def test_worker_death_recovery(cluster):
    """Kill a worker: the coordinator evicts it and re-dispatches its
    fragments — the query still answers (elastic recovery; ref gap G6 is
    'heartbeat recorded, nothing reacts')."""
    coord = cluster["coord"]
    caddr = cluster["addr"]
    extra = Worker(caddr, port=0, heartbeat_interval_s=0.5)
    extra.start()
    time.sleep(0.2)
    assert len(coord.membership.live()) == 3
    extra.shutdown()  # dies silently — no deregistration
    sql = ("SELECT o_status, COUNT(*) AS c FROM orders "
           "GROUP BY o_status ORDER BY o_status")
    client = DistributedClient(caddr)
    got = client.execute(sql)
    _assert_same(got, cluster["local"].execute(sql))
    # the dead worker was evicted on dispatch failure
    assert all(w.addr != extra.address for w in coord.membership.live())
    client.close()


def test_sharded_worker_executes_fragments(cluster):
    """A worker with an explicit 8-device mesh runs fragments through the
    ShardedExecutor — the full multi-host x multi-chip topology in one test
    (coordinator -> worker -> shard_map mesh programs)."""
    from igloo_tpu.parallel.mesh import make_mesh

    coord = cluster["coord"]
    caddr = cluster["addr"]
    w = Worker(caddr, port=0, heartbeat_interval_s=0.5)
    w.server._mesh_setting = make_mesh(8)
    w.start()
    time.sleep(0.3)
    try:
        client = DistributedClient(caddr)
        sql = ("SELECT o_status, COUNT(*) AS c, SUM(o_total) AS s FROM orders "
               "GROUP BY o_status ORDER BY o_status")
        got = client.execute(sql)
        _assert_same(got, cluster["local"].execute(sql))
        client.close()
        from igloo_tpu.parallel.executor import ShardedExecutor
        assert isinstance(w.server._executor(), ShardedExecutor)
    finally:
        w.shutdown()


def test_worker_reregisters_after_eviction(cluster):
    """A worker the coordinator forgot (restart / transient-blip eviction)
    gets ok=false on its next heartbeat and re-registers itself."""
    coord = cluster["coord"]
    wid = cluster["workers"][0].server.worker_id
    coord.membership.evict(wid)
    assert all(w.worker_id != wid for w in coord.membership.live())
    deadline = time.time() + 10
    while time.time() < deadline:
        if any(w.worker_id == wid for w in coord.membership.live()):
            break
        time.sleep(0.1)
    assert any(w.worker_id == wid for w in coord.membership.live())


def test_liveness_sweep_evicts():
    coord = CoordinatorServer("grpc+tcp://127.0.0.1:0", worker_timeout_s=0.5)
    try:
        coord.membership.register("ghost", "grpc+tcp://127.0.0.1:1")
        assert len(coord.membership.live()) == 1
        deadline = time.time() + 5
        while coord.membership.live() and time.time() < deadline:
            time.sleep(0.1)
        assert coord.membership.live() == []
    finally:
        coord.shutdown()


def test_no_workers_falls_back_to_local(tmp_path):
    po, pc, orders, _ = _make_data(tmp_path)
    coord = CoordinatorServer("grpc+tcp://127.0.0.1:0")
    try:
        from igloo_tpu.connectors.parquet import ParquetTable
        coord.register_table("orders", ParquetTable(po))
        client = DistributedClient(f"127.0.0.1:{coord.port}")
        got = client.execute("SELECT COUNT(*) AS c FROM orders")
        assert got.column("c").to_pylist() == [orders.num_rows]
        client.close()
    finally:
        coord.shutdown()


def test_two_process_cluster(tmp_path):
    """Full out-of-process smoke: a worker SUBPROCESS serves fragments for a
    join over the wire (the reference's equivalent path cannot connect, G2)."""
    import subprocess
    import sys

    po, pc, orders, cust = _make_data(tmp_path)
    coord = CoordinatorServer("grpc+tcp://127.0.0.1:0", worker_timeout_s=60.0)
    caddr = f"127.0.0.1:{coord.port}"
    proc = subprocess.Popen(
        [sys.executable, "-m", "igloo_tpu.cluster.worker", caddr],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
    try:
        deadline = time.time() + 60
        while not coord.membership.live() and time.time() < deadline:
            assert proc.poll() is None, proc.stdout.read()
            time.sleep(0.2)
        assert coord.membership.live(), "worker never registered"
        from igloo_tpu.connectors.parquet import ParquetTable
        coord.register_table("orders", ParquetTable(po))
        coord.register_table("cust", ParquetTable(pc))
        client = DistributedClient(caddr)
        sql = ("SELECT c.c_tier, COUNT(*) AS n FROM orders o "
               "JOIN cust c ON o.o_cust = c.c_id "
               "GROUP BY c.c_tier ORDER BY c.c_tier")
        got = client.execute(sql)
        local = QueryEngine()
        local.register_table("orders", ParquetTable(po))
        local.register_table("cust", ParquetTable(pc))
        _assert_same(got, local.execute(sql))
        client.close()
    finally:
        proc.terminate()
        coord.shutdown()
