"""Cluster fault-tolerance tests: RPC policy (retry/backoff/per-call
deadlines), hung-worker recovery, query deadlines + cancellation, and the
fault-injection wiring through real Flight servers.

Everything here runs on tiny tables with use_jit=False (compile-free
fragments) so the file stays in the fast tier; the multi-fault chaos soak is
marked slow. Stub servers model the failure shapes real clusters produce:
a FLAKY peer (unavailable N times, then fine) and a HUNG peer (TCP accepts,
never answers — the failure mode that used to stall queries forever)."""
import json
import threading
import time

import numpy as np
import pyarrow as pa
import pyarrow.flight as flight
import pytest

from igloo_tpu.catalog import MemTable
from igloo_tpu.cluster import faults, rpc
from igloo_tpu.cluster.client import DistributedClient
from igloo_tpu.cluster.coordinator import CoordinatorServer
from igloo_tpu.cluster.worker import Worker, WorkerServer
from igloo_tpu.engine import QueryEngine
from igloo_tpu.errors import DeadlineExceededError, QueryCancelledError
from igloo_tpu.utils import stats, tracing


@pytest.fixture(autouse=True)
def _no_faults():
    faults.clear()
    yield
    faults.clear()


# --- RpcPolicy unit ----------------------------------------------------------


def test_backoff_grows_and_caps():
    p = rpc.RpcPolicy(backoff_base_s=0.1, backoff_max_s=0.4,
                      backoff_jitter=0.0)
    assert [p.backoff_s(a) for a in (1, 2, 3, 4)] == [0.1, 0.2, 0.4, 0.4]
    j = rpc.RpcPolicy(backoff_base_s=0.1, backoff_jitter=0.5)
    steps = {j.backoff_s(1) for _ in range(16)}
    assert len(steps) > 1                      # jitter actually jitters
    assert all(0.05 <= s <= 0.15 for s in steps)


def test_policy_from_env(monkeypatch):
    monkeypatch.setenv("IGLOO_RPC_CALL_TIMEOUT_S", "7.5")
    monkeypatch.setenv("IGLOO_RPC_RETRIES", "5")
    p = rpc.policy_from_env()
    assert p.call_timeout_s == 7.5 and p.retries == 5
    assert p.connect_timeout_s == rpc.RpcPolicy().connect_timeout_s


def test_error_classification():
    assert rpc.retryable(flight.FlightUnavailableError("x"))
    assert rpc.retryable(flight.FlightTimedOutError("x"))
    assert rpc.retryable(ConnectionResetError())
    assert not rpc.retryable(flight.FlightUnauthenticatedError("x"))
    assert not rpc.retryable(flight.FlightServerError("query failed"))
    assert not rpc.retryable(flight.FlightInternalError("x"))
    assert not rpc.retryable(DeadlineExceededError("x"))


def test_config_rpc_section(tmp_path):
    from igloo_tpu.config import Config, rpc_policy
    cfg_file = tmp_path / "igloo.toml"
    cfg_file.write_text(
        "[rpc]\ncall_timeout_s = 9.0\nretries = 4\n"
        "query_deadline_s = 33.0\n")
    cfg = Config.load(str(cfg_file))
    assert cfg.rpc.call_timeout_s == 9.0 and cfg.rpc.retries == 4
    assert cfg.rpc.query_deadline_s == 33.0
    p = rpc_policy(cfg)
    assert p.call_timeout_s == 9.0 and p.retries == 4
    # unset [rpc] keys fall through to the RpcPolicy defaults — the numbers
    # live in cluster/rpc.py ONLY, not in a shadow copy in config.py
    d = rpc.RpcPolicy()
    assert p.connect_timeout_s == d.connect_timeout_s
    assert p.stream_timeout_s == d.stream_timeout_s
    assert p.backoff_base_s == d.backoff_base_s


def test_query_deadline_zero_semantics(monkeypatch):
    from igloo_tpu.cluster.coordinator import DistributedExecutor, Membership
    # a DEFAULT of 0 (env/config) means explicitly unbounded...
    monkeypatch.setenv("IGLOO_QUERY_DEADLINE_S", "0")
    assert DistributedExecutor(Membership()).default_deadline_s is None
    monkeypatch.delenv("IGLOO_QUERY_DEADLINE_S")
    assert DistributedExecutor(
        Membership(), default_deadline_s=0.0).default_deadline_s is None


# --- retry / timeout against stub servers ------------------------------------


class _FlakyServer(flight.FlightServerBase):
    """Unavailable for the first `failures` actions, then healthy."""

    def __init__(self, failures: int):
        super().__init__("grpc+tcp://127.0.0.1:0")
        self.failures_left = failures
        self.calls = 0

    def do_action(self, context, action):
        self.calls += 1
        if self.failures_left > 0:
            self.failures_left -= 1
            raise flight.FlightUnavailableError("flaky: try again")
        return [json.dumps({"ok": True}).encode()]


class _HungServer(flight.FlightServerBase):
    """The hung-worker failure mode: control actions answer instantly but
    `execute_fragment` blocks until shutdown — TCP accepts, never answers."""

    def __init__(self):
        super().__init__("grpc+tcp://127.0.0.1:0")
        self._unhang = threading.Event()
        self.hung_calls = 0
        self.actions: list = []

    def do_action(self, context, action):
        self.actions.append(action.type)
        if action.type == "execute_fragment":
            self.hung_calls += 1
            self._unhang.wait(30)
            raise flight.FlightUnavailableError("hung worker released")
        return [b"{}"]

    def shutdown(self):
        self._unhang.set()
        super().shutdown()


def test_flight_action_retries_unavailable():
    srv = _FlakyServer(failures=2)
    try:
        pol = rpc.RpcPolicy(retries=3, backoff_base_s=0.01,
                            backoff_jitter=0.0)
        with tracing.counter_delta() as delta:
            out = rpc.flight_action(f"127.0.0.1:{srv.port}", "ping",
                                    policy=pol)
        assert out == {"ok": True}
        assert srv.calls == 3
        assert delta.get("rpc.retries") == 2
    finally:
        srv.shutdown()


def test_flight_action_exhausts_retry_budget():
    srv = _FlakyServer(failures=100)
    try:
        pol = rpc.RpcPolicy(retries=1, backoff_base_s=0.01,
                            backoff_jitter=0.0)
        with pytest.raises(flight.FlightUnavailableError):
            rpc.flight_action(f"127.0.0.1:{srv.port}", "ping", policy=pol)
        assert srv.calls == 2  # initial + 1 retry
    finally:
        srv.shutdown()


def test_fatal_errors_do_not_retry():
    class _AppError(flight.FlightServerBase):
        def __init__(self):
            super().__init__("grpc+tcp://127.0.0.1:0")
            self.calls = 0

        def do_action(self, context, action):
            self.calls += 1
            raise flight.FlightServerError("no such table")
    srv = _AppError()
    try:
        with pytest.raises(flight.FlightServerError):
            rpc.flight_action(f"127.0.0.1:{srv.port}", "x",
                              policy=rpc.RpcPolicy(retries=3,
                                                   backoff_base_s=0.01))
        assert srv.calls == 1
    finally:
        srv.shutdown()


def test_hung_server_call_times_out():
    srv = _HungServer()
    try:
        pol = rpc.RpcPolicy(call_timeout_s=0.5, retries=0)
        t0 = time.perf_counter()
        with tracing.counter_delta() as delta:
            with pytest.raises(flight.FlightTimedOutError):
                rpc.flight_action(f"127.0.0.1:{srv.port}",
                                  "execute_fragment", {"id": "x"},
                                  policy=pol)
        assert time.perf_counter() - t0 < 5.0
        assert delta.get("rpc.timeouts") == 1
    finally:
        srv.shutdown()


def test_spent_deadline_fails_before_connecting():
    with tracing.counter_delta() as delta:
        with pytest.raises(DeadlineExceededError):
            rpc.flight_action("127.0.0.1:1", "ping",
                              deadline=time.time() - 1)
    assert delta.get("rpc.deadline_exceeded") == 1


def test_client_side_fault_injection_is_retried():
    """The client-side policy is itself an injection point: an injected
    unavailable on the first attempt is absorbed by the retry budget."""
    srv = _FlakyServer(failures=0)
    try:
        faults.install("client.action.ping:error:1.0:1")
        out = rpc.flight_action(
            f"127.0.0.1:{srv.port}", "ping",
            policy=rpc.RpcPolicy(retries=1, backoff_base_s=0.01))
        assert out == {"ok": True}
    finally:
        srv.shutdown()


def test_store_release_tombstones_late_puts():
    """gRPC deadlines cancel the CALL, not the server handler: an execution
    the coordinator timed out or cancelled still finishes and stores its
    result later. The release tombstone drops that late put — otherwise the
    orphan would sit in worker RSS until process death."""
    from igloo_tpu.cluster.exchange import FragmentStore
    store = FragmentStore(budget_bytes=1 << 20)
    t = pa.table({"a": [1, 2, 3]})
    store.release(["late1"])            # coordinator gave up on it
    with tracing.counter_delta() as delta:
        store.put("late1", t)           # ...the execution finishes anyway
        store.put("__dep_late1:0", t)   # ...as does its dep-slice fetch
    assert "late1" not in store and "__dep_late1:0" not in store
    assert delta.get("exchange.orphan_dropped") == 2
    # a FRESH id (ids are per-query uuids, never reused) stores normally
    store.put("fresh", t)
    assert "fresh" in store


# --- the in-process cluster --------------------------------------------------


N_ROWS = 150_000  # ~3 record batches at the 64Ki stream granularity


def _tables():
    rng = np.random.default_rng(5)
    orders = pa.table({
        "o_id": np.arange(N_ROWS, dtype=np.int64),
        "o_cust": rng.integers(0, 40, N_ROWS),
        "o_total": np.round(rng.random(N_ROWS) * 100, 2),
    })
    cust = pa.table({
        "c_id": np.arange(40, dtype=np.int64),
        "c_tier": pa.array([["gold", "silver"][i % 2] for i in range(40)]),
    })
    return orders, cust


AGG_SQL = ("SELECT o_cust, COUNT(*) AS n, SUM(o_total) AS s FROM orders "
           "GROUP BY o_cust ORDER BY o_cust")
WIDE_SQL = "SELECT o_id, o_total FROM orders"


@pytest.fixture(scope="module")
def cluster():
    orders, cust = _tables()
    coord = CoordinatorServer("grpc+tcp://127.0.0.1:0", worker_timeout_s=60.0,
                              use_jit=False)
    caddr = f"127.0.0.1:{coord.port}"
    workers = [Worker(caddr, port=0, heartbeat_interval_s=0.25,
                      use_jit=False) for _ in range(2)]
    for w in workers:
        # plain per-worker executor: the virtual 8-device mesh adds seconds
        # of first-query setup and is exercised elsewhere (test_cluster.py)
        w.server._mesh_setting = None
        w.start()
    deadline = time.time() + 20
    while len(coord.membership.live()) < 2 and time.time() < deadline:
        time.sleep(0.05)
    assert len(coord.membership.live()) == 2
    coord.register_table("orders", MemTable(orders, partitions=2))
    coord.register_table("cust", MemTable(cust, partitions=2))
    local = QueryEngine(use_jit=False, mesh=None)
    local.register_table("orders", MemTable(orders))
    local.register_table("cust", MemTable(cust))
    try:
        yield {"coord": coord, "addr": caddr, "workers": workers,
               "local": local}
    finally:
        for w in workers:
            w.shutdown()
        coord.shutdown()


def _assert_same(got, want):
    import pandas as pd
    pd.testing.assert_frame_equal(got.to_pandas().reset_index(drop=True),
                                  want.to_pandas().reset_index(drop=True),
                                  check_dtype=False, atol=1e-6)


def test_deadline_happy_path_and_metrics(cluster):
    client = DistributedClient(cluster["addr"])
    got = client.execute(AGG_SQL, deadline_s=60.0, qid="happy1")
    _assert_same(got, cluster["local"].execute(AGG_SQL))
    m = client.last_metrics()
    client.close()
    assert m["qid"] == "happy1" and m["status"] == "ok"
    assert m["deadline_s"] == 60.0
    assert not m["cancelled"] and not m["deadline_exceeded"]


def test_hung_worker_recovered_within_deadline(cluster):
    """THE acceptance check: a worker that accepts TCP but never answers no
    longer stalls the query — its dispatch times out at the RPC deadline,
    it is treated as dead, and re-dispatch completes the query well inside
    the query deadline with recoveries>0."""
    coord = cluster["coord"]
    hung = _HungServer()
    coord.membership.register("hung-stub", f"grpc+tcp://127.0.0.1:{hung.port}")
    old_policy = coord.executor.rpc_policy
    # 3s: an order of magnitude above a healthy dispatch on this fixture
    # (~0.3s warm) so only the stub trips it, far below the query deadline
    coord.executor.rpc_policy = rpc.default_policy().with_(
        call_timeout_s=3.0, connect_timeout_s=3.0, retries=0)
    try:
        t0 = time.perf_counter()
        got = coord.execute_sql(AGG_SQL, deadline_s=30.0)
        elapsed = time.perf_counter() - t0
        _assert_same(got, cluster["local"].execute(AGG_SQL))
        assert hung.hung_calls >= 1, "stub never received a fragment"
        m = coord.executor.last_metrics
        assert m["recoveries"] >= 1
        assert m["status"] == "ok"
        assert elapsed < 15.0, f"query took {elapsed:.1f}s past the hang"
        # the hung worker was evicted like a dead one
        assert all(w.worker_id != "hung-stub"
                   for w in coord.membership.live())
        # ...but end-of-query release still reached it: its handler is STILL
        # running (gRPC deadlines cancel the call, not the handler), and
        # without the release its eventual store.put would leak — the
        # tombstone only exists because _release remembers every addr a
        # fragment was ever dispatched to, not just the reassigned holders
        assert "release" in hung.actions
    finally:
        coord.executor.rpc_policy = old_policy
        coord.membership.evict("hung-stub")
        hung.shutdown()


def _store_ids(worker):
    return [i for i in worker.server._store.ids()]


def test_cancel_mid_stream_releases_results(cluster):
    coord = cluster["coord"]
    out = coord.execute_sql(WIDE_SQL, stream=True, qid="cxl1")
    assert isinstance(out, tuple), "query did not take the distributed path"
    schema, gen = out
    first = next(gen)
    assert first.num_rows > 0
    assert "cxl1" in coord.executor.active_queries()
    assert coord.executor.cancel("cxl1")
    with pytest.raises(QueryCancelledError):
        for _ in gen:
            pass
    # worker-held fragment results are released, not left to run/linger
    deadline = time.time() + 5
    while time.time() < deadline and \
            any(_store_ids(w) for w in cluster["workers"]):
        time.sleep(0.05)
    assert all(not _store_ids(w) for w in cluster["workers"])
    m = coord.executor.last_metrics
    assert m["qid"] == "cxl1" and m["status"] == "cancelled"
    assert m["cancelled"] is True
    assert "cxl1" not in coord.executor.active_queries()
    # surfaced in the query log with a status row
    recs = [q for q in stats.query_log()
            if q.tier == "distributed" and q.status == "cancelled"]
    assert recs and recs[-1].sql == WIDE_SQL


def test_cancel_query_flight_action(cluster):
    client = DistributedClient(cluster["addr"])
    assert client.cancel("no-such-query") is False
    out = cluster["coord"].execute_sql(WIDE_SQL, stream=True, qid="cxl2")
    schema, gen = out
    next(gen)
    assert client.cancel("cxl2") is True
    with pytest.raises(QueryCancelledError):
        for _ in gen:
            pass
    client.close()


def test_query_deadline_exceeded_releases_and_logs(cluster):
    coord = cluster["coord"]
    # a PER-CALL deadline of 0 is a spent budget: expires immediately, never
    # runs unbounded (0 used to be falsy and silently disable the deadline)
    with pytest.raises(DeadlineExceededError):
        coord.execute_sql(AGG_SQL, deadline_s=0.0)
    with tracing.counter_delta() as delta:
        with pytest.raises(DeadlineExceededError, match="deadline"):
            coord.execute_sql(AGG_SQL, deadline_s=0.001)
    assert delta.get("query.deadline_exceeded") == 1
    m = coord.executor.last_metrics
    assert m["status"] == "deadline_exceeded" and m["deadline_exceeded"]
    deadline = time.time() + 5
    while time.time() < deadline and \
            any(_store_ids(w) for w in cluster["workers"]):
        time.sleep(0.05)
    assert all(not _store_ids(w) for w in cluster["workers"])
    recs = [q for q in stats.query_log() if q.status == "deadline_exceeded"]
    assert recs and recs[-1].tier == "distributed"


def test_injected_drop_mid_stream_surfaces(cluster):
    """worker.do_get is wired through faults.wrap_stream: a drop-mid-stream
    rule kills the transfer after one batch the way a vanished peer does."""
    ws = cluster["workers"][0].server
    orders, _ = _tables()
    ws._store.put("dropfrag", orders)
    try:
        faults.install("worker.do_get:drop-mid-stream:1.0:1")
        schema, gen = rpc.flight_stream_batches(
            cluster["workers"][0].address, "dropfrag")
        got = 0
        with pytest.raises(flight.FlightUnavailableError,
                           match="drop-mid-stream"):
            for b in gen:
                got += 1
        assert got == 1
        faults.clear()
        # the store is intact: a re-fetch streams the whole result
        schema, gen = rpc.flight_stream_batches(
            cluster["workers"][0].address, "dropfrag")
        assert sum(b.num_rows for b in gen) == orders.num_rows
    finally:
        faults.clear()
        ws._store.release(["dropfrag"])


def test_injected_drop_mid_stream_on_coordinator_relay(cluster):
    """The coordinator's root-result relay is a streaming point too — a
    drop-mid-stream rule on coordinator.do_get kills the relay after one
    batch, and a no-retry client sees the injected failure, not a hang
    (with its default policy the client now absorbs a transient drop by
    re-fetching from scratch — asserted separately below)."""
    client = DistributedClient(cluster["addr"],
                               policy=rpc.default_policy().with_(retries=0))
    try:
        faults.install("coordinator.do_get:drop-mid-stream:1.0:1")
        with pytest.raises(Exception, match="drop-mid-stream"):
            client.execute(WIDE_SQL)
        faults.clear()
        # the injection consumed its count cap: a re-run streams fully
        _assert_same(client.execute(WIDE_SQL),
                     cluster["local"].execute(WIDE_SQL))
        # default-policy client: ONE injected drop is absorbed by the
        # retry-from-scratch (read_all consumed no partial batches)
        faults.install("coordinator.do_get:drop-mid-stream:1.0:1")
        with DistributedClient(cluster["addr"]) as retrying:
            _assert_same(retrying.execute(WIDE_SQL),
                         cluster["local"].execute(WIDE_SQL))
    finally:
        faults.clear()
        client.close()


def test_bad_typed_query_ticket_is_rejected_cleanly(cluster):
    """Mistyped extended-ticket fields fail as 'bad query ticket', not as
    an opaque TypeError from inside execute_stream; loosely-typed but
    coercible fields (numeric-string deadline, non-string qid) work."""
    cl = rpc.connect(cluster["addr"])
    try:
        with pytest.raises(flight.FlightServerError,
                           match="bad query ticket"):
            cl.do_get(flight.Ticket(json.dumps(
                {"sql": AGG_SQL, "deadline_s": [5]}).encode())).read_all()
        with pytest.raises(flight.FlightServerError,
                           match="bad query ticket"):
            cl.do_get(flight.Ticket(json.dumps(
                {"sql": 7}).encode())).read_all()
        t = cl.do_get(flight.Ticket(json.dumps(
            {"sql": AGG_SQL, "deadline_s": "30", "qid": 7}).encode()
        )).read_all()
        assert t.num_rows > 0
        m = cluster["coord"].executor.last_metrics
        assert m["qid"] == "7" and m["deadline_s"] == 30.0
    finally:
        cl.close()


def test_backoff_does_not_sleep_into_deadline():
    """With less budget left than the next backoff step, the REAL retryable
    error surfaces immediately — not a generic DeadlineExceededError minted
    by the next loop's check after a pointless sleep."""
    srv = _FlakyServer(failures=100)
    try:
        pol = rpc.RpcPolicy(retries=5, backoff_base_s=5.0,
                            backoff_jitter=0.0)
        t0 = time.perf_counter()
        with pytest.raises(flight.FlightUnavailableError, match="flaky"):
            rpc.flight_action(f"127.0.0.1:{srv.port}", "ping", policy=pol,
                              deadline=time.time() + 0.5)
        assert time.perf_counter() - t0 < 3.0   # no 5s backoff sleep
    finally:
        srv.shutdown()


def test_injected_action_errors_recovered(cluster):
    """Server-side injected action errors on execute_fragment look like
    dying workers; the coordinator's recovery still answers the query.
    (The worker is evicted by the injected failure and re-registers on its
    next heartbeat — poll for membership to settle afterwards.)"""
    coord = cluster["coord"]
    try:
        faults.install("worker.do_action.execute_fragment:error:1.0:1")
        got = coord.execute_sql(AGG_SQL, deadline_s=30.0)
        _assert_same(got, cluster["local"].execute(AGG_SQL))
        assert coord.executor.last_metrics["recoveries"] >= 1
    finally:
        faults.clear()
    deadline = time.time() + 10
    while len(coord.membership.live()) < 2 and time.time() < deadline:
        time.sleep(0.05)
    assert len(coord.membership.live()) == 2


# --- worker lifecycle satellites ---------------------------------------------


def test_worker_waits_for_late_coordinator():
    """A worker started BEFORE its coordinator retries registration with
    backoff instead of dying instantly (reference main.rs:37-38 TODO)."""
    import socket
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    w = Worker(f"127.0.0.1:{port}", port=0, heartbeat_interval_s=0.25,
               use_jit=False, register_timeout_s=15.0)
    err: list = []

    def start():
        try:
            w.start()
        except Exception as ex:  # pragma: no cover - the failure mode
            err.append(ex)
    t = threading.Thread(target=start)
    t.start()
    time.sleep(0.6)  # the worker is now inside its retry loop
    coord = CoordinatorServer(f"grpc+tcp://127.0.0.1:{port}",
                              worker_timeout_s=60.0, use_jit=False)
    try:
        t.join(timeout=15)
        assert not t.is_alive() and not err, err
        assert any(ws.worker_id == w.server.worker_id
                   for ws in coord.membership.live())
        assert tracing.counters().get("worker.register_retries", 0) >= 1
    finally:
        w.shutdown()
        coord.shutdown()


def test_worker_gives_up_after_register_timeout():
    w = Worker("127.0.0.1:1", port=0, heartbeat_interval_s=0.25,
               use_jit=False, register_timeout_s=0.7)
    t0 = time.perf_counter()
    try:
        with pytest.raises(Exception):
            w.start()
        assert 0.5 < time.perf_counter() - t0 < 10.0
    finally:
        w.shutdown()


class _HangAllServer(flight.FlightServerBase):
    """Hangs EVERY action — the hung-coordinator shape for registration."""

    def __init__(self):
        super().__init__("grpc+tcp://127.0.0.1:0")
        self._unhang = threading.Event()

    def do_action(self, context, action):
        self._unhang.wait(30)
        raise flight.FlightUnavailableError("released")

    def shutdown(self):
        self._unhang.set()
        super().shutdown()


def test_register_give_up_bounded_against_hung_coordinator():
    """The register deadline bounds each ATTEMPT's gRPC timeout too: a
    coordinator that accepts TCP but never answers must not stretch the
    documented give-up to call_timeout_s x attempts (minutes)."""
    srv = _HangAllServer()
    w = Worker(f"127.0.0.1:{srv.port}", port=0, heartbeat_interval_s=0.25,
               use_jit=False, register_timeout_s=1.0)
    t0 = time.perf_counter()
    try:
        with pytest.raises(Exception):
            w.start()
        assert time.perf_counter() - t0 < 10.0
    finally:
        w.shutdown()
        srv.shutdown()


def test_qid_reuse_does_not_clobber_newer_token():
    from igloo_tpu.cluster.coordinator import (CancelToken,
                                               DistributedExecutor,
                                               Membership)
    ex = DistributedExecutor(Membership())
    old, new = CancelToken(), CancelToken()
    ex._queries["q"] = new          # a retried query re-registered the qid
    ex._unregister("q", old)        # the OLD query's late cleanup fires
    assert ex._queries.get("q") is new  # newer query stays cancellable
    ex._unregister("q", new)
    assert "q" not in ex._queries


def test_heartbeat_logs_first_failure_once(cluster, capsys):
    w = cluster["workers"][1]
    real = w._coordinator_action

    def failing(name, payload):
        raise ConnectionResetError("synthetic outage")
    w._coordinator_action = failing
    try:
        time.sleep(1.2)  # ~5 heartbeat intervals of failure
        err = capsys.readouterr().err
        assert err.count("heartbeat") == 1, err  # the edge, not the repeats
        assert "failing" in err
    finally:
        w._coordinator_action = real
    deadline = time.time() + 5
    recovered = ""
    while time.time() < deadline and "recovered" not in recovered:
        recovered += capsys.readouterr().err
        time.sleep(0.1)
    assert "recovered" in recovered
    assert w._hb_down is False


# --- chaos soak --------------------------------------------------------------


def test_chaos_soak_worker_kill_plus_action_errors(cluster):
    """Multi-fault soak: probabilistic execute_fragment errors under a
    seeded spec while a third worker dies mid-query — every query still
    answers correctly, with recoveries observed across the run."""
    coord = cluster["coord"]
    extra = Worker(cluster["addr"], port=0, heartbeat_interval_s=0.25,
                   use_jit=False)
    extra.start()
    deadline = time.time() + 10
    while len(coord.membership.live()) < 3 and time.time() < deadline:
        time.sleep(0.05)
    want = cluster["local"].execute(AGG_SQL)
    recoveries = 0
    try:
        faults.install("worker.do_action.execute_fragment:error:0.15",
                       seed=11)
        for i in range(6):
            if i == 2:
                extra.shutdown()  # silent death mid-run
            got = coord.execute_sql(AGG_SQL, deadline_s=60.0)
            _assert_same(got, want)
            recoveries += coord.executor.last_metrics["recoveries"]
    finally:
        faults.clear()
        extra.shutdown()
    assert recoveries >= 1
    deadline = time.time() + 10
    while len(coord.membership.live()) < 2 and time.time() < deadline:
        time.sleep(0.05)
    assert len(coord.membership.live()) >= 2
